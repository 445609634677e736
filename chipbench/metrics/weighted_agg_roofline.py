"""``kernels/weighted_agg.py``'s share of its roofline: the least time
the chip could take for the kernel's calls in the window (bytes or
operations at the peaks, whichever bounds), over the summed device time
of the kernel's events.  One call reads the ``[S, N]`` client matrix
and the ``[S]`` weights and writes the ``[N]`` aggregate in f32, with
a multiply and an add per element of the matrix."""

NEEDLE = "weighted_agg"


def cost(s: int, n: int):
    """``(bytes, operations)`` of one call."""
    return (s * n + s + n) * 4, 2 * s * n


def read(ctx):
    calls, secs = ctx["trace"].kernel(NEEDLE)
    if not calls or secs <= 0 or ctx["peaks"] is None:
        return None
    b, f = cost(ctx["recipe"]["S"], ctx["config"]["model"]["num_params"])
    p = ctx["peaks"]
    least = max(b / p["hbm_bytes_per_s"], f / p["bf16_flops"])
    return 100.0 * calls * least / secs
