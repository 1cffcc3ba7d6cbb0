"""Optimizer / checkpoint / data / sharding-rule substrate tests."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common.checkpoint import latest_step, restore_checkpoint, save_checkpoint
from repro.training.data import DataConfig, MarkovTokens
from repro.training.optimizer import (adam_init, adam_update, apply_updates,
                                      clip_by_global_norm, cosine_schedule)


def test_adam_matches_reference():
    """One Adam step on a scalar against hand math."""
    p = {"w": jnp.asarray([2.0])}
    g = {"w": jnp.asarray([0.5])}
    st = adam_init(p)
    upd, st = adam_update(g, st, p, lr=0.1, b1=0.9, b2=0.999, eps=1e-8)
    # m=0.05 -> mhat=0.5 ; v=0.00025/0.001 -> vhat=0.25 ; u = 0.5/(0.5+eps)=~1
    np.testing.assert_allclose(np.asarray(upd["w"]), [-0.1], rtol=1e-4)
    p2 = apply_updates(p, upd)
    np.testing.assert_allclose(np.asarray(p2["w"]), [1.9], rtol=1e-4)


def test_adam_converges_quadratic():
    p = {"w": jnp.asarray([5.0, -3.0])}
    st = adam_init(p)
    for _ in range(400):
        g = jax.grad(lambda q: jnp.sum(q["w"] ** 2))(p)
        upd, st = adam_update(g, st, p, lr=0.05)
        p = apply_updates(p, upd)
    assert float(jnp.max(jnp.abs(p["w"]))) < 1e-2


def test_clip_by_global_norm():
    g = {"a": jnp.asarray([3.0]), "b": jnp.asarray([4.0])}
    clipped, norm = clip_by_global_norm(g, 1.0)
    np.testing.assert_allclose(float(norm), 5.0, rtol=1e-6)
    total = jnp.sqrt(clipped["a"] ** 2 + clipped["b"] ** 2)
    np.testing.assert_allclose(float(total[0]), 1.0, rtol=1e-5)


def test_cosine_schedule():
    assert float(cosine_schedule(0, 1.0, 10, 100)) == 0.0
    assert float(cosine_schedule(10, 1.0, 10, 100)) == pytest.approx(1.0)
    assert float(cosine_schedule(100, 1.0, 10, 100)) == pytest.approx(0.1, abs=1e-3)


def test_checkpoint_roundtrip(tmp_path):
    tree = {"a": jnp.arange(6).reshape(2, 3).astype(jnp.float32),
            "b": {"c": jnp.ones((4,), jnp.int32)}}
    d = str(tmp_path / "ck")
    save_checkpoint(d, 7, tree)
    assert latest_step(d) == 7
    restored = restore_checkpoint(d, tree)
    for x, y in zip(jax.tree_util.tree_leaves(tree),
                    jax.tree_util.tree_leaves(restored)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_markov_data_learnable_structure():
    cfg = DataConfig(vocab_size=64, seq_len=32, batch_size=4, branching=2)
    data = MarkovTokens(cfg)
    b = data.sample_batch()
    assert b["tokens"].shape == (4, 32)
    assert b["labels"].shape == (4, 32)
    # successors constrained to the branching table
    ok = 0
    for row_t, row_l in zip(b["tokens"], b["labels"]):
        for t, l in zip(row_t, row_l):
            ok += l in data.successors[t]
    assert ok == 4 * 32


# -------------------------------------------------------------- sharding
def test_partition_rules():
    from jax.sharding import PartitionSpec as P
    from repro.launch.mesh import make_debug_mesh
    from repro.sharding.specs import spec_for

    mesh = make_debug_mesh(1, 1)
    assert spec_for("embed/table", (1024, 256), mesh) == P("model", "data")
    assert spec_for("periods/blk0_attn/wq/w", (4, 256, 512), mesh) == \
        P(None, "data", "model")
    assert spec_for("periods/blk0_moe/gate", (4, 16, 256, 64), mesh) == \
        P(None, "model", "data")   # trailing None trimmed
    assert spec_for("periods/norm0_mix/scale", (4, 256), mesh) == P()


def test_partition_divisibility_degrades():
    from jax.sharding import PartitionSpec as P
    from repro.launch.mesh import make_debug_mesh
    from repro.sharding.specs import spec_for
    # fake a 16-wide axis via mesh shape check: use debug mesh (1,1): always divides
    mesh = make_debug_mesh(1, 1)
    # odd vocab still maps (axis size 1 divides everything on debug mesh)
    assert spec_for("embed/table", (51865, 768), mesh) == P("model", "data")


def test_batch_spec_degrades():
    from repro.launch.mesh import make_debug_mesh
    from repro.sharding.specs import batch_spec
    mesh = make_debug_mesh(1, 1)
    assert batch_spec(mesh, 16) == ("data",)
    # batch=1 divides a 1-wide axis, so it stays
    assert batch_spec(mesh, 1) == ("data",)


# ---------------------------------------------------------- compile cache
@pytest.fixture
def cache_dir_config():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_compile_cache_honours_the_environment(monkeypatch,
                                               cache_dir_config, tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, JAX's own reading of it stands:
    the helper reports that directory and sets no other."""
    from repro.common import compile_cache as CC
    monkeypatch.setenv(CC.ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert CC.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_falls_back_to_the_checkout(monkeypatch,
                                                  cache_dir_config):
    """Unset, the cache goes to one fixed, gitignored directory at the root
    of the checkout."""
    from repro.common import compile_cache as CC
    monkeypatch.delenv(CC.ENV_VAR, raising=False)
    root = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    want = os.path.join(root, ".jax_cache")
    assert CC.enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    with open(os.path.join(root, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
