"""Layer scopes of the round program and the op-to-layer table.

The round body names its layers with ``jax.named_scope``
(``repro.utils.spans``); XLA carries the scope into the ``op_name``
metadata of the instructions, fusions included, built from the scoped
ops, and ``spans.op_layers`` reads the table back from the compiled
text.  The persistent compilation cache is off in this module: its key
leaves metadata out, so a program compiled before the scopes existed
would be served in place of this one.
"""
import re

import jax
import pytest

from _helpers import init_mlp_params, mlp_accuracy, mlp_loss
from repro.core import AggregationConfig
from repro.data.synthetic import make_synth_femnist
from repro.federated import FedAvgStrategy
from repro.federated.simulation import FederatedSimulation, FedSimConfig
from repro.utils import spans

HLO = """HloModule jit_run_block, entry_computation_layout={()->f32[]}

%fused_computation (param_0: f32[4]) -> f32[4] {
  %param_0 = f32[4]{0} parameter(0)
  ROOT %tanh.1 = f32[4]{0} tanh(%param_0), metadata={op_name="jit(run_block)/while/body/fedsim.local_train/vmap(jit(step))/tanh"}
}

ENTRY %main (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  %fusion.3 = f32[4]{0} fusion(%p), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(run_block)/while/body/fedsim.local_train/tanh" stack_frame_id=4}
  %divergence_sq.2 = f32[8,1]{1,0} custom-call(%fusion.3), custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata={}}, metadata={op_name="jit(run_block)/fedsim.criteria/jit(divergence_sq)/divergence_sq/pallas_call"}
  %dot.7 = f32[6,4]{1,0} dot(%p, %p), metadata={op_name="jit(run_block)/fedsim.aggregate/fedsim.adjust/dot_general"}
  %copy.1 = f32[4]{0} copy(%p)
  ROOT %fusion.9 = f32[4]{0} fusion(%copy.1), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(run_block)/fedsim.eval/jit(acc)/reduce_sum"}
}
"""


def test_layer_is_the_innermost_fedsim_scope():
    assert spans.layer_of("jit(f)/fedsim.aggregate/fedsim.adjust/dot") \
        == "adjust"
    assert spans.layer_of("jit(f)/fedsim.eval/jit(g)/reduce") == "eval"
    assert spans.layer_of("jit(f)/while/body/iota") == spans.UNSCOPED
    assert spans.layer_of("jit(f)/fedsim.nope/iota") == spans.UNSCOPED
    with pytest.raises(ValueError):
        with spans.layer("nope"):
            pass


def test_op_layers_parses_compiled_text():
    tab = spans.op_layers(HLO)
    assert spans.module_name(HLO) == "jit_run_block"
    assert tab == {"param_0": "unscoped", "tanh.1": "local_train",
                   "p": "unscoped", "fusion.3": "local_train",
                   "divergence_sq.2": "criteria", "dot.7": "adjust",
                   "copy.1": "unscoped", "fusion.9": "eval"}


def test_model_part_is_the_innermost_model_scope(no_persistent_cache):
    assert spans.part_of("jit(f)/model.mamba/model.ssd/mul") == "ssd"
    # differentiation wraps the scope's name
    assert spans.part_of(
        "jit(f)/vmap(transpose(jvp(model.mlp)))/dot_general") == "mlp"
    assert spans.part_of(
        "jit(f)/checkpoint/rematted_computation/model.attention/exp") \
        == "attention"
    assert spans.part_of("jit(f)/fedsim.eval/model.logits/reduce") \
        == "logits"
    # any part a model names, and only as a whole scope
    assert spans.part_of("jit(f)/model.experts/model.mlpx/add") == "mlpx"
    assert spans.part_of("jit(f)/fedsim.local_train/add") is None
    assert spans.part_of("jit(f)/mymodel.mlp/add") is None
    assert spans.part_of("jit(f)/model.mlp.w/add") is None

    def f(x):
        with spans.part("experts"):
            return jax.numpy.tanh(x) * 2.0

    text = jax.jit(f).lower(jax.numpy.ones(4)).compile().as_text()
    assert set(spans.op_layers(text).scopes.values()) == {"experts"}


def test_op_table_carries_the_model_parts_apart():
    text = HLO.replace("fedsim.local_train/tanh",
                       "fedsim.local_train/model.mamba/model.ssd/tanh")
    tab = spans.op_layers(text)
    assert tab == spans.op_layers(HLO)        # the layers are unchanged
    assert tab.scopes == {"fusion.3": "ssd"}
    assert spans.op_layers(HLO).scopes == {}


@pytest.fixture(scope="module")
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache

    on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", on)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def small_data():
    return make_synth_femnist(num_clients=16, mean_samples=20, seed=3)


CASES = {
    # sync + Algorithm-1 on the flat path, Md streaming the divergence
    "flat_sync_adjust": (dict(
        flat_params=True, online_adjust=True,
        aggregation=AggregationConfig(criteria=("Md", "Ds", "Ld"),
                                      priority=(0, 1, 2))),
        set(spans.LAYERS)),
    "flat_fedavg": (dict(flat_params=True, strategy=FedAvgStrategy()),
                    set(spans.LAYERS) - {"adjust"}),
    "pytree_sync": (dict(flat_params=False),
                    set(spans.LAYERS) - {"adjust"}),
}


@pytest.mark.parametrize("case", list(CASES))
def test_round_block_carries_its_layer_scopes(case, small_data,
                                              no_persistent_cache):
    kw, want = CASES[case]
    cfg = FedSimConfig(fraction=0.25, batch_size=8, local_epochs=1, lr=0.1,
                       max_rounds=1, **kw)
    sim = FederatedSimulation(small_data,
                              init_mlp_params(jax.random.key(0), hidden=32),
                              mlp_loss, mlp_accuracy, cfg)
    module, tab = sim.op_layers()
    assert module == "jit_run_block"
    assert set(tab.values()) - {spans.UNSCOPED} == want
    # every fusion the block runs is in the table, under a layer or none
    text = sim._run_block.lower(
        sim.init_state(), jax.numpy.arange(1, 2)).compile().as_text()
    fusions = re.findall(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = [^=]*?\sfusion\(",
                         text, re.M)
    assert fusions
    assert all(tab[f] in spans.LAYERS + (spans.UNSCOPED,) for f in fusions)
