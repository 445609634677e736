"""``kernels/divergence.py``'s share of its roofline: the least time the
chip could take for the kernel's calls in the window (bytes or
operations at the peaks, whichever bounds), over the summed device time
of the kernel's events.  One call reads the ``[S, N]`` client matrix
and the ``[N]`` global vector in f32 and does a subtract, a square and
an add per element."""

NEEDLE = "divergence_sq"


def cost(s: int, n: int):
    """``(bytes, operations)`` of one call."""
    return (s * n + n) * 4, 3 * s * n


def read(ctx):
    calls, secs = ctx["trace"].kernel(NEEDLE)
    if not calls or secs <= 0 or ctx["peaks"] is None:
        return None
    b, f = cost(ctx["recipe"]["S"], ctx["config"]["model"]["num_params"])
    p = ctx["peaks"]
    least = max(b / p["hbm_bytes_per_s"], f / p["bf16_flops"])
    return 100.0 * calls * least / secs
