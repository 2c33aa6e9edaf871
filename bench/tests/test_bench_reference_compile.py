"""The plain reference's round, compiled for one chip of a described TPU
v5e, holds no P-sized buffer that a larger configuration could not
afford: its gradient takes a round's records one at a time, and its
noise and inertia update draws the Laplace noise without a P-sized
temporary. Sizes are xlstm-125m's 8 blocks (`configs/xlstm-125m.json`,
P = 157,037,596) at seq 1024, unless said otherwise.

Round gradient, `memory_analysis().temp_size_in_bytes` at B = 1, 2, 4
(XLA:TPU for a described v5e). Records side by side, a vmap that holds
the (B, P) float32 gradients and B records' activations: 1,319,420,416,
2,554,088,960, 5,051,950,592 B. One at a time, as `Reference` takes
them: 861,483,520, 1,826,410,496, 1,826,410,496 B. One 4P is
628,150,384 B. From B = 2 on the records run in a loop, whose fixed cost
the program with one record, which has no loop, does not pay; so the
test compares B = 2 with B = 4.

Round gradient at one published Zamba2-2.7B period's leaves (P =
501,156,000), seq 2048, B = 4, with a loss whose activations are nil:
arguments, temporaries and outputs together 9,925,277,184 B, 4.95 x 4P
(the records side by side: 16,566,579,712 B, 8.26 x 4P). At most five
P-sized float32 buffers: the weights, their leaves, one record's gradient
tree and its flat copy, and the running sum. At xlstm-125m's leaves the
same program reads 5.47 x 4P: the sLSTM's (768, 4, 192, 4) input weight,
its minor 4 padded to 128 lanes, would account for the 0.47.

Update: bits drawn over (rows, 1024) and sliced to P, 3,145,857,024 B of
temporaries; drawn over (P,), 129,024 B.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library at a time, and every test worker
imports this file.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference
from bench.cell import model_config
from bench.harness import load_module
from bench.tests.tiny_cell import BENCH

SEQ = 1024


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_persistent_cache():
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one; keep the cache out of it.
    from jax.experimental.compilation_cache import compilation_cache
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    compilation_cache.reset_cache()


ROUND = reference.Round(n_owners=2, horizon=10, clip=1.0, sigma=0.01,
                        theta_max=100.0, target_lr=0.05, records=[10, 12],
                        epsilons=[1.0, 2.0])


@pytest.fixture(scope="module")
def xlstm():
    """xlstm-125m's configuration file and its parameters' shapes."""
    from repro.models import build_model
    m = json.loads((BENCH / "configs" / "xlstm-125m.json").read_text())
    model = build_model(model_config(m["model"]), remat=False)
    return m, jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), jnp.float32))


@pytest.fixture(scope="module")
def xlstm_ref(xlstm):
    m, shapes = xlstm
    loss = load_module(BENCH / "configs" / "xlstm-125m.py").lm_loss
    ref = reference.Reference(m["model"], loss, shapes, ROUND)
    assert ref.layout.size == 157_037_596
    return ref


# One period of Zamba2-2.7B as published (6 Mamba2 layers, the shared
# attention and gated-GELU block over 2 x 2560, the rank-128 adapter, the
# hybrid layer's linear, the tied embedding): its leaves' shapes.
_MAMBA2 = {"in_proj": (2560, 10448), "conv": (4, 5248), "conv_b": (5248,),
           "dt": (80,), "A": (80,), "D": (80,), "norm": (5120,),
           "out_proj": (5120, 2560), "ln": (2560,)}
ZAMBA2_PERIOD = {
    "embed": (32000, 2560), "ln_f": (2560,),
    "mamba": [dict(_MAMBA2) for _ in range(6)],
    "shared": {"q": (5120, 5120), "k": (5120, 5120), "v": (5120, 5120),
               "o": (5120, 2560), "ln1": (5120,), "ln2": (2560,),
               "gate_up": (2560, 20480), "down": (10240, 2560)},
    "adapter": {"a": (2560, 128), "b": (128, 20480)},
    "linear": (2560, 2560)}


def _no_activations(params, tokens, labels, model):
    """A loss with a gradient on every parameter and no activations to
    speak of: what the round holds besides one record's activations."""
    c = (jnp.mean(tokens.astype(jnp.float32))
         + jnp.mean(labels.astype(jnp.float32)))
    return c * sum(jnp.sum(p * p) for p in jax.tree_util.tree_leaves(params))


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _f32_bytes(n):
    return n * np.dtype(np.float32).itemsize


def test_round_gradient_takes_records_one_at_a_time(one_chip, xlstm_ref,
                                                    no_persistent_cache):
    P = xlstm_ref.layout.size

    def temp_bytes(batch):
        tokens = jax.ShapeDtypeStruct((batch, SEQ), jnp.int32,
                                      sharding=one_chip)
        theta = jax.ShapeDtypeStruct((P,), jnp.float32, sharding=one_chip)
        with jax.default_matmul_precision("highest"):     # as `follow`
            compiled = xlstm_ref._grads.lower(theta, tokens,
                                              tokens).compile()
        return compiled.memory_analysis().temp_size_in_bytes

    two, four = temp_bytes(2), temp_bytes(4)
    assert four - two < _f32_bytes(P), (two, four)


def test_round_gradient_holds_at_most_five_p_sized_buffers(
        one_chip, no_persistent_cache):
    shapes = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s, jnp.float32), ZAMBA2_PERIOD,
        is_leaf=lambda s: isinstance(s, tuple))
    ref = reference.Reference({}, _no_activations, shapes, ROUND)
    P = ref.layout.size
    assert P == 501_156_000
    theta = jax.ShapeDtypeStruct((P,), jnp.float32, sharding=one_chip)
    tokens = jax.ShapeDtypeStruct((4, 2048), jnp.int32, sharding=one_chip)
    with jax.default_matmul_precision("highest"):
        mem = ref._grads.lower(theta, tokens, tokens).compile(
            ).memory_analysis()
    held = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert held <= 5 * _f32_bytes(P), held / _f32_bytes(P)


def test_update_draws_noise_without_a_p_sized_temporary(
        one_chip, xlstm_ref, no_persistent_cache):
    P = xlstm_ref.layout.size

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    scalar = arg((), jnp.float32)
    compiled = xlstm_ref._update.lower(
        arg((P,), jnp.float32), arg((P,), jnp.float32),
        arg((2,), jnp.uint32), scalar, scalar, scalar).compile()
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < _f32_bytes(P), temp
