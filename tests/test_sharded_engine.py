"""Mesh-sharded federation engine + owner-parallel grouped rounds.

Contracts under test (ISSUE 4):

  * Sharding rules: `flat_shardings` puts bank rows on the data axes and P
    on 'model' (folding the data axes into P when N does not divide),
    degrading to replication when nothing divides.
  * 1x1-mesh parity: the sharded engine reproduces the unsharded flat
    path BIT-FOR-BIT (params, bank, ledger, metrics) under the same keys.
  * Multi-device (the CI job forces 8 host devices via XLA_FLAGS): same
    refusal pattern and reconciled ledger EXACTLY; numerics to float
    tolerance (GSPMD reduction order); state stays sharded after
    run_rounds (no gather to one device).
  * Owner-parallel mode: conflict-free partition invariants; ledger spend
    exactly equal to the sequential scan; max_group=1 falls back to the
    sequential scan bit-for-bit; bounded theta_L divergence otherwise.
  * `Federation.reconcile` on sharded states: bit-exact fold, drift and
    superseded-snapshot errors still raised.

On a single-device host every mesh in here is 1x1 — the sharded code
paths still execute (constraints, device_put layouts), the specs just
degrade to replication. The CI `sharded-smoke` job runs this file under
XLA_FLAGS=--xla_force_host_platform_device_count=8 so the real
multi-device branches are exercised on every PR without TPU hardware.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.federation import (DataOwner, Federation, FederationConfig,
                              LedgerDriftError, ParamFlat, PrivatizerConfig,
                              pack_groups, partition_conflict_free)
from repro.launch.mesh import make_host_mesh
from repro.sharding.rules import flat_axes, flat_bank_spec, flat_shardings

N_OWNERS, K = 8, 24
MULTI_DEVICE = len(jax.devices()) > 1


@pytest.fixture(scope="module", autouse=True)
def _partitionable_rng():
    # Multi-device RNG contract: partitionable threefry makes every draw
    # invariant under sharding (the legacy lowering re-associates the
    # counters when GSPMD partitions it, changing the drawn values).
    # Module-scoped save/restore: the rest of the suite keeps the
    # default stream.
    old = jax.config.jax_threefry_partitionable
    jax.config.update("jax_threefry_partitionable", True)
    yield
    jax.config.update("jax_threefry_partitionable", old)


@pytest.fixture(scope="module")
def toy():
    key = jax.random.PRNGKey(0)
    # P = 6*4 + 4 = 28: NOT divisible by 8 — on the 8-device mesh the
    # theta spec degrades (model=2 divides, data folding doesn't), which
    # is exactly the degrade path the rules promise.
    params = {"w": jax.random.normal(key, (6, 4)), "b": jnp.zeros((4,))}
    batches = {"x": jax.random.normal(jax.random.PRNGKey(1), (K, 4, 6)),
               "y": jax.random.normal(jax.random.PRNGKey(2), (K, 4, 4))}
    def loss_fn(p, b):
        return jnp.mean((b["x"] @ p["w"] + p["b"] - b["y"]) ** 2)
    priv = PrivatizerConfig(xi=1.0, granularity="example")
    return params, batches, loss_fn, priv


def _make_fed(loss_fn, priv, horizon=3, mesh=None, **kw):
    owners = [DataOwner(n=100, epsilon=1.0, xi=1.0)
              for _ in range(N_OWNERS)]
    fed = Federation(owners, FederationConfig(horizon=horizon, sigma=1e-2,
                                              theta_max=10.0, lr_scale=5.0))
    fed.make_step(loss_fn, privatizer=priv, pack_params=True, mesh=mesh,
                  **kw)
    return fed


# ------------------------------ rules ---------------------------------------
def test_flat_axes_prefers_owner_rows_on_data():
    from jax.sharding import AbstractMesh
    mesh = AbstractMesh((4, 2), ("data", "model"))
    n_ax, p_ax = flat_axes(mesh, n_owners=8, p=64)
    assert n_ax == ("data",) and p_ax == ("model",)
    assert flat_bank_spec(mesh, 8, 64) == jax.sharding.PartitionSpec(
        ("data",), ("model",))


def test_flat_axes_folds_data_into_p_when_owners_dont_divide():
    from jax.sharding import AbstractMesh
    mesh = AbstractMesh((4, 2), ("data", "model"))
    n_ax, p_ax = flat_axes(mesh, n_owners=3, p=64)   # 3 % 4 != 0
    assert n_ax is None and p_ax == ("model", "data")
    # and degrades to replication when nothing divides
    n_ax, p_ax = flat_axes(mesh, n_owners=3, p=7)
    assert n_ax is None and p_ax is None


def test_flat_axes_multi_pod_data_axes():
    from jax.sharding import AbstractMesh
    mesh = AbstractMesh((2, 2, 2), ("pod", "data", "model"))
    n_ax, p_ax = flat_axes(mesh, n_owners=8, p=64)
    assert n_ax == ("pod", "data") and p_ax == ("model",)


def test_partition_conflict_free_invariants():
    seq = [0, 1, 2, 0, 1, 0, 0, 3]
    groups = partition_conflict_free(seq)
    assert groups == [(0, 3), (3, 2), (5, 1), (6, 2)]
    # every group: consecutive, distinct owners; concatenation == seq
    flat = []
    for start, length in groups:
        chunk = seq[start:start + length]
        assert len(set(chunk)) == len(chunk)
        flat.extend(chunk)
    assert flat == seq
    assert partition_conflict_free(seq, max_group=1) == [
        (i, 1) for i in range(len(seq))]
    assert partition_conflict_free([]) == []
    with pytest.raises(ValueError, match="max_group"):
        partition_conflict_free(seq, max_group=0)


def test_pack_groups_layout():
    idx, valid = pack_groups([(0, 3), (3, 1), (4, 2)])
    np.testing.assert_array_equal(idx, [[0, 1, 2], [3, 0, 0], [4, 5, 0]])
    np.testing.assert_array_equal(valid, [[True, True, True],
                                          [True, False, False],
                                          [True, True, False]])
    idx0, valid0 = pack_groups([])
    assert idx0.shape == (0, 1) and valid0.shape == (0, 1)


# ----------------------- sharded-state parity -------------------------------
def _run_pair(toy, mesh, bank_dtype=None):
    params, batches, loss_fn, priv = toy
    seq = jax.random.randint(jax.random.PRNGKey(3), (K,), 0, N_OWNERS)
    root = jax.random.PRNGKey(4)
    fed_u = _make_fed(loss_fn, priv, bank_dtype=bank_dtype)
    fed_s = _make_fed(loss_fn, priv, mesh=mesh, bank_dtype=bank_dtype)
    s_u, m_u = fed_u.run_rounds(fed_u.init_state(params), batches, seq,
                                key=root)
    s_s, m_s = fed_s.run_rounds(fed_s.init_state(params), batches, seq,
                                key=root)
    return fed_u, fed_s, s_u, s_s, m_u, m_s


def test_one_by_one_mesh_is_bit_exact(toy):
    # The sharded engine on a trivial mesh IS the PR 3 flat path: same
    # trace modulo no-op constraints, bit-for-bit outputs.
    from repro.launch.mesh import make_debug_mesh
    fed_u, fed_s, s_u, s_s, m_u, m_s = _run_pair(toy, make_debug_mesh(1, 1))
    np.testing.assert_array_equal(np.asarray(s_u.theta_L.buf),
                                  np.asarray(s_s.theta_L.buf))
    np.testing.assert_array_equal(np.asarray(s_u.bank), np.asarray(s_s.bank))
    for name in m_u:
        np.testing.assert_array_equal(np.asarray(m_u[name]),
                                      np.asarray(m_s[name]))
    assert fed_s.reconcile(s_s) == fed_u.reconcile(s_u)


def test_host_mesh_parity_and_residency(toy):
    # Whatever this host offers (1 device locally, 8 in the CI smoke job):
    # exact refusals + ledger, float-tolerance numerics, and the state
    # keeps its mesh layout after the scan — run_rounds never gathered the
    # bank to one device.
    mesh = make_host_mesh(model=2 if len(jax.devices()) % 2 == 0 else 1)
    fed_u, fed_s, s_u, s_s, m_u, m_s = _run_pair(toy, mesh)
    np.testing.assert_array_equal(np.asarray(m_u["refused"]),
                                  np.asarray(m_s["refused"]))
    np.testing.assert_allclose(np.asarray(s_u.theta_L.buf),
                               np.asarray(s_s.theta_L.buf),
                               rtol=2e-5, atol=2e-6)
    assert fed_s.reconcile(s_s) == fed_u.reconcile(s_u)
    assert set(s_s.bank.sharding.mesh.axis_names) == {"data", "model"}
    if MULTI_DEVICE:
        assert len(s_s.bank.sharding.device_set) == len(jax.devices())
        assert not s_s.bank.is_fully_replicated


@pytest.mark.skipif(not MULTI_DEVICE, reason="needs the forced 8-device "
                    "host (CI sharded-smoke job)")
def test_bank_rows_actually_shard_across_devices(toy):
    params, _, loss_fn, priv = toy
    mesh = make_host_mesh(model=2)
    fed = _make_fed(loss_fn, priv, mesh=mesh)
    state = fed.init_state(params)
    spec = state.bank.sharding.spec
    assert spec[0] in ("data", ("data",))  # owner rows over the data axis
    shard_rows = {s.data.shape[0] for s in state.bank.addressable_shards}
    assert shard_rows == {N_OWNERS // mesh.shape["data"]}
    # theta replicates over data, shards P over model when divisible
    p = state.theta_L.size
    assert state.theta_L.buf.sharding.spec == (
        flat_shardings(mesh, N_OWNERS, p).theta.spec)


def test_bf16_bank_works_sharded(toy):
    mesh = make_host_mesh(model=2 if len(jax.devices()) % 2 == 0 else 1)
    fed_u, fed_s, s_u, s_s, m_u, m_s = _run_pair(toy, mesh,
                                                 bank_dtype=jnp.bfloat16)
    assert s_s.bank.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(m_u["refused"]),
                                  np.asarray(m_s["refused"]))
    assert fed_s.reconcile(s_s) == fed_u.reconcile(s_u)


def test_bf16_bank_under_grouped_owner_parallel(toy):
    # the bf16 bank previously only ran through the sequential scan in
    # this suite; the grouped driver must keep the quantized-row
    # semantics: exact refusal pattern and ledger spend vs the bf16
    # sequential scan, rows written back in bf16, bounded theta deviation
    params, batches, loss_fn, priv = toy
    seq = jax.random.randint(jax.random.PRNGKey(3), (K,), 0, N_OWNERS)
    root = jax.random.PRNGKey(4)
    fed_s = _make_fed(loss_fn, priv, bank_dtype=jnp.bfloat16)
    fed_g = _make_fed(loss_fn, priv, bank_dtype=jnp.bfloat16)
    s_s, m_s = fed_s.run_rounds(fed_s.init_state(params), batches, seq,
                                key=root)
    s_g, m_g = fed_g.run_rounds(fed_g.init_state(params), batches, seq,
                                key=root, owner_parallel=True)
    assert s_g.bank.dtype == jnp.bfloat16
    assert int(np.asarray(m_s["refused"]).sum()) > 0
    np.testing.assert_array_equal(np.asarray(m_s["refused"]),
                                  np.asarray(m_g["refused"]))
    np.testing.assert_array_equal(np.asarray(s_s.ledger.spent),
                                  np.asarray(s_g.ledger.spent))
    assert fed_g.reconcile(s_g) == fed_s.reconcile(s_s)
    g = np.asarray(s_g.theta_L.buf)
    assert np.isfinite(g).all()
    assert np.max(np.abs(np.asarray(s_s.theta_L.buf) - g)) < 2.0
    # and the grouped driver composes with a mesh on the bf16 bank
    mesh = make_host_mesh(model=2 if len(jax.devices()) % 2 == 0 else 1)
    fed_m = _make_fed(loss_fn, priv, mesh=mesh, bank_dtype=jnp.bfloat16)
    s_m, m_m = fed_m.run_rounds(fed_m.init_state(params), batches, seq,
                                key=root, owner_parallel=True)
    np.testing.assert_array_equal(np.asarray(m_s["refused"]),
                                  np.asarray(m_m["refused"]))
    assert fed_m.reconcile(s_m) == fed_g.ledger()


@pytest.mark.parametrize("fmt", ["int8", "fp8"])
def test_quant_bank_works_sharded(toy, fmt):
    # the QuantBank bundle (codes/scales/residual) under flat_shardings:
    # codes rows over the data axes, scales rows likewise, residual laid
    # out exactly like theta; refusals and reconciled ledger exact vs the
    # unsharded quantized engine
    from repro.federation import QuantBank
    mesh = make_host_mesh(model=2 if len(jax.devices()) % 2 == 0 else 1)
    fed_u, fed_s, s_u, s_s, m_u, m_s = _run_pair(toy, mesh, bank_dtype=fmt)
    assert isinstance(s_s.bank, QuantBank)
    np.testing.assert_array_equal(np.asarray(m_u["refused"]),
                                  np.asarray(m_s["refused"]))
    assert fed_s.reconcile(s_s) == fed_u.reconcile(s_u)
    assert np.isfinite(np.asarray(s_s.theta_L.buf)).all()
    if MULTI_DEVICE:
        assert s_s.bank.codes.sharding.spec[0] in ("data", ("data",))
        assert s_s.bank.scales.sharding.spec[0] in ("data", ("data",))
        # the residual lives exactly where theta lives (they add)
        assert (s_s.bank.residual.sharding.spec
                == s_s.theta_L.buf.sharding.spec)


# ------------------- reconcile on sharded states ----------------------------
def test_sharded_reconcile_folds_bit_exactly_and_detects_drift(toy):
    params, batches, loss_fn, priv = toy
    mesh = make_host_mesh()
    fed = _make_fed(loss_fn, priv, horizon=2, mesh=mesh)
    state = fed.init_state(params)
    b0 = jax.tree_util.tree_map(lambda a: a[0], batches)
    key = jax.random.PRNGKey(0)
    for _ in range(2):                     # spend owner 0's cap host-side
        state, m = fed.step(state, b0, 0, key)
        assert not m["refused"]
    # device counters fold back bit-exactly through the sharded state
    led = fed.reconcile(state)
    assert led[0]["responses"] == 2 and led[0]["exhausted"]
    # ...and a STALE device ledger (snapshot predates host-side spend) is
    # still refused loudly, sharded or not
    fed2 = _make_fed(loss_fn, priv, horizon=2, mesh=mesh)
    st2 = fed2.init_state(params)
    for _ in range(2):
        st2, _ = fed2.step(st2, b0, 0, key)
    seq = jnp.zeros(2, jnp.int32)
    st2, ms = fed2.run_rounds(
        st2, jax.tree_util.tree_map(lambda a: a[:2], batches), seq,
        key=jax.random.PRNGKey(1))
    assert not np.asarray(ms["refused"]).any()    # stale ledger grants
    with pytest.raises(LedgerDriftError, match="stale"):
        fed2.reconcile(st2)


def test_sharded_superseded_snapshot_cannot_reconcile(toy):
    params, batches, loss_fn, priv = toy
    mesh = make_host_mesh()
    fed = _make_fed(loss_fn, priv, mesh=mesh)        # horizon (cap) = 3
    def sub(n):
        return jax.tree_util.tree_map(lambda a: a[:n], batches)
    state_a = fed.init_state(params)
    state_a, _ = fed.run_rounds(state_a, sub(8), jnp.zeros(8, jnp.int32),
                                key=jax.random.PRNGKey(1))
    state_b = fed.init_state(params)                 # supersedes state_a
    state_b, _ = fed.run_rounds(state_b, sub(4), jnp.zeros(4, jnp.int32),
                                key=jax.random.PRNGKey(2))
    led = fed.reconcile(state_b)
    assert led[0]["responses"] == 3 and led[0]["refused"] == 1
    with pytest.raises(LedgerDriftError, match="superseded"):
        fed.reconcile(state_a)


# ------------------------ owner-parallel mode -------------------------------
def test_owner_parallel_ledger_spend_matches_sequential(toy):
    # the acceptance bar: grouped execution never changes WHO answered and
    # WHO was refused — the privacy spend is the sequential scan's, exactly
    params, batches, loss_fn, priv = toy
    seq = jax.random.randint(jax.random.PRNGKey(3), (K,), 0, N_OWNERS)
    root = jax.random.PRNGKey(4)
    fed_s = _make_fed(loss_fn, priv)
    fed_g = _make_fed(loss_fn, priv)
    s_s, m_s = fed_s.run_rounds(fed_s.init_state(params), batches, seq,
                                key=root)
    s_g, m_g = fed_g.run_rounds(fed_g.init_state(params), batches, seq,
                                key=root, owner_parallel=True)
    assert int(np.asarray(m_s["refused"]).sum()) > 0       # exhaustion bites
    np.testing.assert_array_equal(np.asarray(m_s["refused"]),
                                  np.asarray(m_g["refused"]))
    np.testing.assert_array_equal(np.asarray(m_s["owner"]),
                                  np.asarray(m_g["owner"]))
    np.testing.assert_array_equal(np.asarray(s_s.ledger.spent),
                                  np.asarray(s_g.ledger.spent))
    np.testing.assert_array_equal(np.asarray(s_s.ledger.refused),
                                  np.asarray(s_g.ledger.refused))
    assert int(s_s.step) == int(s_g.step)
    assert fed_g.reconcile(s_g) == fed_s.reconcile(s_s)
    # bounded deviation, not garbage: both stay in Theta and close-ish
    g = np.asarray(s_g.theta_L.buf)
    assert np.isfinite(g).all() and np.abs(g).max() <= 10.0
    assert np.max(np.abs(np.asarray(s_s.theta_L.buf) - g)) < 2.0


def test_owner_parallel_max_group_one_is_bit_exact(toy):
    # size-1 groups == the sequential scan (run_rounds literally routes to
    # it), so the owner-parallel surface degrades to exact semantics
    params, batches, loss_fn, priv = toy
    seq = jax.random.randint(jax.random.PRNGKey(3), (K,), 0, N_OWNERS)
    root = jax.random.PRNGKey(4)
    fed_s = _make_fed(loss_fn, priv)
    fed_g = _make_fed(loss_fn, priv)
    s_s, m_s = fed_s.run_rounds(fed_s.init_state(params), batches, seq,
                                key=root)
    s_g, m_g = fed_g.run_rounds(fed_g.init_state(params), batches, seq,
                                key=root, owner_parallel=True, max_group=1)
    np.testing.assert_array_equal(np.asarray(s_s.theta_L.buf),
                                  np.asarray(s_g.theta_L.buf))
    np.testing.assert_array_equal(np.asarray(s_s.bank), np.asarray(s_g.bank))
    for name in m_s:
        np.testing.assert_array_equal(np.asarray(m_s[name]),
                                      np.asarray(m_g[name]))


def test_owner_parallel_metrics_come_back_in_round_order(toy):
    params, batches, loss_fn, priv = toy
    # a schedule with a long conflict-free prefix and repeats after
    seq = jnp.asarray(list(range(N_OWNERS)) * (K // N_OWNERS), jnp.int32)
    fed = _make_fed(loss_fn, priv, horizon=K)
    s, m = fed.run_rounds(fed.init_state(params), batches, seq,
                          key=jax.random.PRNGKey(4), owner_parallel=True)
    np.testing.assert_array_equal(np.asarray(m["owner"]), np.asarray(seq))
    assert m["clip_frac"].shape == (K,)
    assert not np.asarray(m["refused"]).any()


def test_owner_parallel_on_tree_state(toy):
    # the grouped driver is representation-generic: pytree states vmap
    # through the same body
    params, batches, loss_fn, priv = toy
    seq = jax.random.randint(jax.random.PRNGKey(3), (K,), 0, N_OWNERS)
    root = jax.random.PRNGKey(4)
    owners = [DataOwner(n=100, epsilon=1.0, xi=1.0)] * N_OWNERS
    fed = Federation(owners, FederationConfig(horizon=3, sigma=1e-2,
                                              theta_max=10.0, lr_scale=5.0))
    fed.make_step(loss_fn, privatizer=priv)          # tree representation
    s, m = fed.run_rounds(fed.init_state(params), batches, seq, key=root,
                          owner_parallel=True)
    assert not isinstance(s.theta_L, ParamFlat)
    fed_ref = _make_fed(loss_fn, priv)
    s_ref, m_ref = fed_ref.run_rounds(fed_ref.init_state(params), batches,
                                      seq, key=root, owner_parallel=True)
    np.testing.assert_array_equal(np.asarray(m["refused"]),
                                  np.asarray(m_ref["refused"]))
    assert fed.reconcile(s) == fed_ref.reconcile(s_ref)


def test_owner_parallel_with_fused_kernel_and_mesh(toy):
    # the production stack end to end: dp_round kernel path + bf16 bank +
    # host mesh + grouped schedule
    params, batches, loss_fn, _ = toy
    priv = PrivatizerConfig(xi=1e-3, granularity="microbatch",
                            n_microbatches=2, fused_kernel=True,
                            kernel_block_rows=8)
    mesh = make_host_mesh()
    fed = _make_fed(loss_fn, priv, horizon=2, mesh=mesh,
                    bank_dtype=jnp.bfloat16)
    seq = jnp.asarray(np.arange(K) % 4, jnp.int32)      # owners 0-3, 6 each
    s, ms = fed.run_rounds(fed.init_state(params), batches, seq,
                           key=jax.random.PRNGKey(6), owner_parallel=True)
    assert np.isfinite(np.asarray(s.theta_L.buf)).all()
    granted = ~np.asarray(ms["refused"])
    assert granted.sum() == 8                           # 2 per owner cap
    led = fed.reconcile(s)
    assert all(led[i]["responses"] == 2 and led[i]["refused"] == 4
               for i in range(4))


def test_kernel_backend_on_host_mesh_matches_one_device(toy):
    # The Pallas kernels (here through the interpreter, as the compiled
    # Mosaic kernel would be) cannot be partitioned by GSPMD: on a
    # multi-device mesh they run per device over replicated operands,
    # and the result equals the one-device mesh's.
    params, batches, loss_fn, _ = toy
    priv = PrivatizerConfig(xi=1e-3, granularity="microbatch",
                            n_microbatches=2, fused_kernel=True,
                            kernel_block_rows=8, kernel_interpret=True)
    from repro.launch.mesh import make_debug_mesh
    seq = jnp.asarray(np.arange(K) % 4, jnp.int32)
    runs = []
    for mesh in (make_host_mesh(), make_debug_mesh(1, 1)):
        fed = _make_fed(loss_fn, priv, horizon=2, mesh=mesh,
                        bank_dtype=jnp.bfloat16)
        s, m = fed.run_rounds(fed.init_state(params), batches, seq,
                              key=jax.random.PRNGKey(6))
        runs.append((np.asarray(s.theta_L.buf), np.asarray(m["refused"]),
                     fed.reconcile(s)))
    (theta_m, refused_m, led_m), (theta_1, refused_1, led_1) = runs
    np.testing.assert_array_equal(refused_m, refused_1)
    assert led_m == led_1
    np.testing.assert_allclose(theta_m, theta_1, rtol=2e-5, atol=2e-6)


def test_sharded_row_gather_is_exact():
    # The owner-row gather on a bank sharded by rows is a masked sum,
    # not a dynamic index (which all-gathers the whole bank); it returns
    # the row's exact values.
    from repro.federation.deep import _gather_row
    from repro.launch.mesh import make_debug_mesh
    mesh = make_host_mesh()
    bank = jax.random.normal(jax.random.PRNGKey(0),
                             (N_OWNERS, 64)).astype(jnp.bfloat16)
    sh = flat_shardings(mesh, N_OWNERS, 64)
    if MULTI_DEVICE:
        assert sh.bank.spec[0] is not None
    masked = sh._replace(bank=jax.sharding.NamedSharding(
        make_debug_mesh(1, 1), jax.sharding.PartitionSpec("data")))
    for idx in (0, 3, N_OWNERS - 1):
        for rule in (sh, masked):
            row = jax.jit(_gather_row, static_argnums=2)(bank, idx, rule)
            np.testing.assert_array_equal(np.asarray(row, np.float32),
                                          np.asarray(bank[idx], np.float32))


def test_owner_parallel_repeat_dispatches_reuse_compile_cache(toy):
    # schedule-drawn partitions differ per dispatch; the session pads
    # (n_groups, G_max) to stable buckets so a serving loop doesn't
    # recompile the K-round scan every call
    params, batches, loss_fn, priv = toy
    fed = _make_fed(loss_fn, priv, horizon=K)
    state = fed.init_state(params)
    for seed in range(4):
        seq = jax.random.randint(jax.random.PRNGKey(seed), (K,), 0,
                                 N_OWNERS)
        state, m = fed.run_rounds(state, batches, seq,
                                  key=jax.random.PRNGKey(10 + seed),
                                  owner_parallel=True, max_group=4)
        assert m["refused"].shape == (K,)
    # compiles are bounded by the power-of-two group buckets straddled
    # (here 2: n_groups lands on both sides of a boundary across seeds),
    # NOT one per dispatch
    assert fed._group_fn._cache_size() <= 2


def test_mesh_requires_flat_engine(toy):
    params, _, loss_fn, priv = toy
    owners = [DataOwner(n=100, epsilon=1.0, xi=1.0)] * N_OWNERS
    fed = Federation(owners, FederationConfig(horizon=3, sigma=1e-2))
    with pytest.raises(ValueError, match="flat-engine option"):
        fed.make_step(loss_fn, privatizer=priv, mesh=make_host_mesh())
    fed.make_step(loss_fn, privatizer=priv)
    with pytest.raises(ValueError, match="flat-engine option"):
        fed.init_state(params, mesh=make_host_mesh())
