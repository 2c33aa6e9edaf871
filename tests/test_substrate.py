"""Substrate: data pipeline, optimizers, checkpointing, sharding rules,
HLO cost walker, clocks."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import latest_step, load_checkpoint, save_checkpoint
from repro.configs import all_configs, get_config
from repro.core.clocks import owner_counts, poisson_schedule, uniform_schedule
from repro.data import (OwnerDataPipeline, health, lending, owner_shards,
                        synthetic_owner_shards)
from repro.optim import adamw, apply_updates, constant, cosine_decay, sgd


# ------------------------------ data --------------------------------------
def test_generators_shapes_and_bounds():
    for gen in (lending, health):
        X, y = gen(1000, seed=1)
        assert X.shape == (1000, 10) and y.shape == (1000,)
        assert np.isfinite(X).all() and np.isfinite(y).all()
        assert np.abs(X).max() <= 3.0 + 1e-9


def test_owner_shards_partition():
    sizes = [100, 250, 50]
    shards = owner_shards("lending", sizes, seed=0)
    assert [s[0].shape[0] for s in shards] == sizes
    # heterogeneity=0 gives exchangeable owners; >0 shifts the local y|x
    homog = owner_shards("lending", [200, 200], seed=0, heterogeneity=0.0)
    het = owner_shards("lending", [200, 200], seed=0, heterogeneity=1.0)
    # same X marginals per seed, different targets under heterogeneity
    np.testing.assert_allclose(homog[0][0], het[0][0])
    assert not np.allclose(homog[0][1], het[0][1])


def test_owner_pipeline_cycles():
    shards = synthetic_owner_shards(3, 8, 16, 100, seed=0)
    pipe = OwnerDataPipeline(shards, batch=4, seed=0)
    it = iter(pipe)
    owners = [next(it)[0] for _ in range(50)]
    assert set(owners) == {0, 1, 2}
    b = shards[0].next_batch(4)
    assert b["tokens"].shape == (4, 16)
    np.testing.assert_array_equal(b["labels"],
                                  np.roll(b["tokens"], -1, axis=1))


# ------------------------------ clocks -------------------------------------
def test_poisson_schedule_statistics(rng_key):
    sched = poisson_schedule(rng_key, n_owners=5, horizon=5000)
    assert float(sched.times[0]) >= 0
    assert bool(jnp.all(jnp.diff(sched.times) >= 0))      # ordered
    counts = np.asarray(owner_counts(sched.owners, 5))
    assert counts.min() > 5000 / 5 * 0.8                   # near-uniform
    # superposed rate = N -> mean gap 1/N
    gaps = np.diff(np.asarray(sched.times))
    assert np.mean(gaps) == pytest.approx(1 / 5, rel=0.1)


def test_uniform_schedule_matches_poisson_marginals(rng_key):
    s = uniform_schedule(rng_key, 4, 8000)
    counts = np.bincount(np.asarray(s), minlength=4)
    assert counts.min() > 8000 / 4 * 0.85


# ------------------------------ optim --------------------------------------
@pytest.mark.parametrize("opt", ["sgd", "momentum", "adamw"])
def test_optimizers_minimize_quadratic(opt, rng_key):
    target = jnp.asarray([1.0, -2.0, 0.5])
    params = {"w": jnp.zeros(3)}
    def loss(p):
        return jnp.sum((p["w"] - target) ** 2)
    if opt == "sgd":
        init, update = sgd(constant(0.1))
    elif opt == "momentum":
        init, update = sgd(constant(0.05), momentum=0.9)
    else:
        init, update = adamw(constant(0.1))
    state = init(params)
    for _ in range(200):
        g = jax.grad(loss)(params)
        upd, state = update(g, state, params)
        params = apply_updates(params, upd)
    assert float(loss(params)) < 1e-3


def test_cosine_schedule_endpoints():
    f = cosine_decay(1.0, total=100, warmup=10)
    assert float(f(jnp.int32(0))) == pytest.approx(0.0)
    assert float(f(jnp.int32(10))) == pytest.approx(1.0)
    assert float(f(jnp.int32(100))) == pytest.approx(0.0, abs=1e-6)


# ------------------------------ checkpoint ---------------------------------
def test_checkpoint_roundtrip(tmp_path, rng_key):
    from repro.core.async_trainer import AsyncDPConfig, init_state
    from repro.core.dp_sgd import PrivatizerConfig
    params = {"w": jax.random.normal(rng_key, (4, 4)),
              "blocks": [{"a": jnp.ones((2,))}, {"a": jnp.zeros((2,))}]}
    acfg = AsyncDPConfig(n_owners=2, horizon=10, epsilons=(1., 1.),
                         owner_sizes=(10, 10),
                         privatizer=PrivatizerConfig(xi=1.0))
    state = init_state(params, acfg)
    d = str(tmp_path / "ckpt")
    save_checkpoint(d, 7, state)
    assert latest_step(d) == 7
    like = init_state(jax.tree_util.tree_map(jnp.zeros_like, params), acfg)
    restored = load_checkpoint(d, 7, like)
    for a, b in zip(jax.tree_util.tree_leaves(restored),
                    jax.tree_util.tree_leaves(state)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b))


# ------------------------------ sharding rules -----------------------------
def _abstract_mesh(shape, names):
    from jax.sharding import AbstractMesh
    return AbstractMesh(tuple(shape), tuple(names))


@pytest.mark.parametrize("arch", sorted(all_configs()))
def test_param_specs_divisibility(arch, rng_key):
    """Every sharded axis must divide its dim on the production mesh."""
    from repro.models import build_model
    from repro.sharding import param_specs

    cfg = get_config(arch)
    model = build_model(cfg)
    sds = jax.eval_shape(lambda k: model.init(k, jnp.bfloat16),
                         jax.random.PRNGKey(0))
    mesh = _abstract_mesh((16, 16), ("data", "model"))
    specs = param_specs(sds, cfg, mesh)

    def check(path, leaf, spec):
        assert len(spec) <= len(leaf.shape), (path, spec, leaf.shape)
        for dim, ax in zip(leaf.shape, tuple(spec) + (None,) * 8):
            if ax is None:
                continue
            size = int(np.prod([mesh.shape[a] for a in
                                (ax if isinstance(ax, tuple) else (ax,))]))
            assert dim % size == 0, (path, spec, leaf.shape)

    jax.tree_util.tree_map_with_path(
        lambda p, leaf, s: check(p, leaf, s), sds, specs)


def test_hlo_cost_walker_known_workload():
    from repro.analysis.hlo_cost import analyze
    L, B, D, F = 4, 8, 32, 64

    def f(w1, w2, x):
        def body(h, ws):
            a, b = ws
            return jnp.tanh(h @ a) @ b, None
        h, _ = jax.lax.scan(body, x, (w1, w2))
        return jnp.sum(h)

    w1 = jnp.ones((L, D, F), jnp.float32)
    w2 = jnp.ones((L, F, D), jnp.float32)
    x = jnp.ones((B, D), jnp.float32)
    comp = jax.jit(f).lower(w1, w2, x).compile()
    res = analyze(comp.as_text())
    fwd = L * 2 * (2 * B * D * F)
    assert res["flops"] == pytest.approx(fwd, rel=0.05)
    assert res["traffic_bytes"] > 0
