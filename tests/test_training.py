"""Training substrate: optimizer math, loss descent, checkpoint/restore
(+elastic), data pipeline determinism, sharding specs."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.configs import ARCHS
from repro.models import init_params
from repro.training import optimizer as O
from repro.resilience.checkpoint import Checkpointer
from repro.training.data import DataCfg, SyntheticLM, make_dataset
from repro.training.shardspec import param_pspecs
from repro.training.train_step import IGNORE, cross_entropy, make_train_step

KEY = jax.random.PRNGKey(0)


def test_adamw_matches_reference():
    """Our AdamW vs a hand-rolled numpy reference on a tiny problem."""
    opt = O.OptCfg(lr=1e-2, warmup_steps=0, total_steps=100, b1=0.9, b2=0.99,
                   weight_decay=0.0, clip_norm=1e9, mixed_precision=False)
    p0 = {"w": jnp.asarray([1.0, -2.0, 3.0])}
    state = O.init_state(p0, opt)
    g = {"w": jnp.asarray([0.1, 0.2, -0.3])}
    state, m1 = O.apply_updates(state, g, opt)
    # reference
    lr = float(O.schedule(1, opt))
    gn = np.asarray([0.1, 0.2, -0.3])
    m = 0.1 * gn
    v = 0.01 * gn ** 2
    mh = m / (1 - 0.9)
    vh = v / (1 - 0.99)
    want = np.asarray([1.0, -2.0, 3.0]) - lr * mh / (np.sqrt(vh) + opt.eps)
    np.testing.assert_allclose(np.asarray(state["params"]["w"]), want, rtol=1e-5)


def test_cross_entropy_ignore_index():
    logits = jnp.zeros((1, 4, 8))
    labels = jnp.asarray([[1, 2, IGNORE, IGNORE]])
    ce = cross_entropy(logits, labels)
    np.testing.assert_allclose(float(ce), np.log(8), rtol=1e-5)


@pytest.mark.parametrize("mixed", [False, True])
def test_loss_decreases(mixed):
    """A few steps on a tiny llama must reduce loss on a FIXED batch."""
    cfg = ARCHS["llama3-8b"].reduced()
    opt = O.OptCfg(lr=5e-3, warmup_steps=0, total_steps=50,
                   mixed_precision=mixed, clip_norm=1.0)
    params = init_params(KEY, cfg, max_seq=16)
    state = O.init_state(params, opt)
    step = jax.jit(make_train_step(cfg, opt))
    toks = jax.random.randint(KEY, (4, 17), 0, cfg.vocab)
    batch = {"inputs": toks[:, :-1], "labels": toks[:, 1:]}
    losses = []
    for _ in range(8):
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0]
    assert all(np.isfinite(losses))


def test_grad_compression_mode_runs():
    cfg = ARCHS["llama3-8b"].reduced()
    opt = O.OptCfg(lr=1e-3, grad_compress_bf16=True, mixed_precision=True)
    params = init_params(KEY, cfg, max_seq=8)
    state = O.init_state(params, opt)
    step = jax.jit(make_train_step(cfg, opt))
    toks = jax.random.randint(KEY, (2, 9), 0, cfg.vocab)
    state, metrics = step(state, {"inputs": toks[:, :-1], "labels": toks[:, 1:]})
    assert np.isfinite(float(metrics["loss"]))


def test_checkpoint_roundtrip(tmp_path):
    cfg = ARCHS["h2o-danube-1.8b"].reduced()
    opt = O.OptCfg(mixed_precision=True)
    state = O.init_state(init_params(KEY, cfg, max_seq=8), opt)
    ck = Checkpointer(str(tmp_path))
    ck.save(state, step=7, extra={"data": {"step": 3}})
    assert ck.latest_step() == 7
    like = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), state)
    restored, step = ck.restore(like)
    assert step == 7
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert ck.extra()["data"]["step"] == 3


def test_checkpoint_async_and_commit_marker(tmp_path):
    state = {"w": jnp.arange(8.0), "step": jnp.int32(1)}
    ck = Checkpointer(str(tmp_path), async_save=True)
    ck.save(state, step=1)
    ck.wait()
    assert ck.latest_step() == 1
    # a partially-written checkpoint (no COMMIT) must be ignored
    import os
    os.makedirs(tmp_path / "step_9")
    assert ck.latest_step() == 1


def test_elastic_restore_new_mesh(tmp_path):
    """Restore re-shards onto a different mesh (here: trivial 1-dev mesh but
    through the NamedSharding path — the elastic mechanism)."""
    from repro.launch.elastic import plan_mesh
    from repro.training.shardspec import named
    state = {"w": jnp.arange(16.0).reshape(4, 4)}
    ck = Checkpointer(str(tmp_path))
    ck.save(state, step=1)
    plan = plan_mesh(n_chips=1, model_parallel=1)
    mesh = plan.make()
    from jax.sharding import PartitionSpec as P
    shardings = named(mesh, {"w": P(None, None)})
    like = {"w": jax.ShapeDtypeStruct((4, 4), jnp.float32)}
    restored, _ = ck.restore(like, shardings=shardings)
    assert np.array_equal(np.asarray(restored["w"]), np.arange(16.0).reshape(4, 4))


def test_mesh_plans():
    from repro.launch.elastic import plan_mesh, shrink_after_failure
    plan = plan_mesh(512, model_parallel=16, pods=2)
    assert plan.shape == (2, 16, 16)
    smaller = shrink_after_failure(plan, lost_chips=16)
    assert np.prod(smaller.shape) <= 512 - 16
    assert smaller.shape[-1] == 16  # TP preserved


def test_data_determinism_and_restore():
    cfg = DataCfg(batch=2, seq=8, vocab=100, seed=3)
    it1 = SyntheticLM(cfg)
    b1 = [next(it1) for _ in range(3)]
    it2 = SyntheticLM(DataCfg(batch=2, seq=8, vocab=100, seed=3))
    it2.restore({"step": 2, "seed": 3})
    b2 = next(it2)
    assert np.array_equal(b1[2]["inputs"], b2["inputs"])
    assert (b1[0]["inputs"] != b1[1]["inputs"]).any()
    assert np.array_equal(b1[0]["inputs"][:, 1:], b1[0]["labels"][:, :-1])


def test_token_file_pipeline(tmp_path):
    toks = (np.arange(10_000) % 250).astype(np.uint16)
    path = tmp_path / "tokens.bin"
    toks.tofile(path)
    cfg = DataCfg(batch=2, seq=16, vocab=256, path=str(path))
    ds = make_dataset(cfg)
    b = next(ds)
    assert b["inputs"].shape == (2, 16)
    assert np.array_equal(b["inputs"][:, 1:], b["labels"][:, :-1])


def test_param_pspecs_divisibility():
    """Specs never request a non-dividing axis (GQA kv=8 on TP=16 etc.)."""
    import os
    from repro.launch.mesh import make_mesh
    if len(jax.devices()) < 1:
        pytest.skip("no devices")
    cfg = ARCHS["llama3-8b"].reduced()
    params = jax.eval_shape(lambda: init_params(KEY, cfg, max_seq=8))
    mesh = make_mesh((1,), ("model",))
    specs = param_pspecs(params, mesh)
    # every spec entry must divide the corresponding dim
    def check(path, leaf, spec):
        for d, e in zip(leaf.shape, spec):
            if e is None:
                continue
            axes = e if isinstance(e, tuple) else (e,)
            prod = 1
            for a in axes:
                prod *= mesh.shape[a]
            assert d % prod == 0
    jax.tree_util.tree_map_with_path(
        lambda p, l, s: check(p, l, s), params, specs)


def test_grad_accumulation_matches_full_batch():
    """accum_steps=k on a batch must produce the same update as the full
    batch in one shot (same loss gradient, fp32 accumulation)."""
    cfg = ARCHS["llama3-8b"].reduced()
    opt = O.OptCfg(lr=1e-3, warmup_steps=0, clip_norm=1e9,
                   mixed_precision=False)
    params = init_params(KEY, cfg, max_seq=8)
    toks = jax.random.randint(KEY, (4, 9), 0, cfg.vocab)
    batch = {"inputs": toks[:, :-1], "labels": toks[:, 1:]}
    s1 = O.init_state(params, opt)
    s2 = jax.tree.map(lambda a: a, s1)
    step1 = jax.jit(make_train_step(cfg, opt, accum_steps=1))
    step2 = jax.jit(make_train_step(cfg, opt, accum_steps=2))
    s1, m1 = step1(s1, batch)
    s2, m2 = step2(s2, batch)
    assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-4
    for a, b in zip(jax.tree.leaves(s1["params"]), jax.tree.leaves(s2["params"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-6)
