"""Compile rehearsals: every Pallas kernel of the main path, compiled for
one chip of a described TPU v5e at the full width of xlstm-125m.

Nothing runs: the TPU compiler is asked to accept each kernel at the
(rows, 1024) layout the flat engine hands it, and the compiled program
must hold the kernel (`tpu_custom_call`), not a stand-in. Interpret-mode
tests cannot see what this sees: tiling alignment, casts the chip has no
instruction for, and VMEM limits.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library at a time, and every test
worker imports this file.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.kernels.bank_codec.kernel import absmax_2d, decode_2d, encode_2d
from repro.kernels.dp_clip_noise.kernel import (LANES, dp_round_2d,
                                                scale_noise_2d, sqnorm_2d)
from repro.kernels.tree_noise.kernel import tree_delta_2d

BLOCK_ROWS = 256        # PrivatizerConfig.kernel_block_rows / codec default
TREE_BLOCK_ROWS = 64    # tree_noise default (the whole depth axis in VMEM)
TREE_DEPTH = 3


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


@pytest.fixture(scope="module")
def xlstm_p():
    """Parameter count of xlstm-125m at its published widths."""
    from repro.configs import get_config
    from repro.models import build_model
    model = build_model(get_config("xlstm-125m"), remat=False)
    shapes = jax.eval_shape(lambda k: model.init(k, jnp.float32),
                            jax.random.PRNGKey(0))
    return sum(leaf.size for leaf in jax.tree_util.tree_leaves(shapes))


def _rows(p, block_rows):
    """Rows of the (rows, LANES) view the ops pad a (P,) buffer to."""
    per_block = block_rows * LANES
    return -(-p // per_block) * block_rows


def _cases(p, one_chip):
    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    rows = _rows(p, BLOCK_ROWS)
    f32 = arg((rows, LANES), jnp.float32)
    bits = arg((rows, LANES), jnp.uint32)
    scalar = arg((1, 1), jnp.float32)
    t_rows = _rows(p, TREE_BLOCK_ROWS)
    return {
        "dp_round_2d": (
            lambda tb, acc, u, gn, ns, w: dp_round_2d(
                tb, acc, u, gn, ns, w, sigma=1e-2, lr_own=0.05, lr_l=0.04,
                n_owners=8, theta_max=100.0),
            (f32, f32, bits, scalar, scalar, scalar)),
        "scale_noise_2d": (scale_noise_2d, (f32, bits, scalar, scalar)),
        "sqnorm_2d": (sqnorm_2d, (f32,)),
        "absmax_2d": (absmax_2d, (f32,)),
        "encode_2d_int8": (lambda x, u, s: encode_2d(x, u, s, "int8"),
                           (f32, bits, scalar)),
        "encode_2d_fp8": (lambda x, u, s: encode_2d(x, u, s, "fp8"),
                          (f32, bits, scalar)),
        "decode_2d_int8": (lambda c, s: decode_2d(c, s, "int8"),
                           (arg((rows, LANES), jnp.int8), scalar)),
        "decode_2d_fp8": (lambda c, s: decode_2d(c, s, "fp8"),
                          (arg((rows, LANES), jnp.uint8), scalar)),
        "tree_delta_2d": (
            tree_delta_2d,
            (arg((TREE_DEPTH, t_rows, LANES), jnp.float32),
             arg((t_rows, LANES), jnp.uint32), arg((1, 1), jnp.int32),
             scalar)),
    }


KERNELS = ["dp_round_2d", "scale_noise_2d", "sqnorm_2d", "absmax_2d",
           "encode_2d_int8", "encode_2d_fp8", "decode_2d_int8",
           "decode_2d_fp8", "tree_delta_2d"]
# The `name=` of each case's Pallas call: the compiled custom call carries
# it in its op_name, where profiles and the benchmark's stages find it.
KERNEL_NAMES = {"dp_round_2d": "dp_round", "scale_noise_2d": "scale_noise",
                "sqnorm_2d": "squared_norm", "absmax_2d": "bank_absmax",
                "encode_2d_int8": "bank_encode",
                "encode_2d_fp8": "bank_encode",
                "decode_2d_int8": "bank_decode",
                "decode_2d_fp8": "bank_decode", "tree_delta_2d": "tree_delta"}


@pytest.mark.parametrize("name", KERNELS)
def test_kernel_compiles_for_v5e_at_xlstm_width(name, topo,
                                                no_persistent_cache,
                                                xlstm_p):
    from jax.sharding import SingleDeviceSharding
    one_chip = SingleDeviceSharding(topo.devices[0])
    fn, args = _cases(xlstm_p, one_chip)[name]
    compiled = jax.jit(fn).lower(*args).compile()
    calls = [line for line in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert calls
    for line in calls:
        assert f'/{KERNEL_NAMES[name]}/pallas_call"' in line, line[:300]


def test_cases_cover_every_listed_kernel(xlstm_p):
    # the parametrization and the case table must not drift apart
    assert sorted(_cases(xlstm_p, None)) == sorted(KERNELS)
    assert sorted(KERNEL_NAMES) == sorted(KERNELS)
    assert xlstm_p > 100_000_000        # published widths, not reduced()
