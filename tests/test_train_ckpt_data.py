"""Training substrate: optimizer, microbatching, compression, checkpoint
atomicity/restart/elastic-remesh, data pipeline determinism."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ckpt import checkpoint as ckpt
from repro.configs import get_config
from repro.data.pipeline import DataConfig, TokenStream
from repro.models.model import build_model
from repro.train.loop import LoopConfig, run
from repro.train.optimizer import (OptConfig, adamw_init, adamw_update,
                                   compress_grads, dequantize_int8, lr_at,
                                   quantize_int8)
from repro.train.step import build_train_step, init_state

CFG = get_config("tinyllama-1.1b").reduced(
    n_layers=2, d_model=64, n_heads=2, n_kv_heads=2, head_dim=32,
    d_ff=128, vocab=128, vocab_pad_to=64)


def small_setup(microbatches=1, **opt_kw):
    model = build_model(CFG)
    opt = OptConfig(lr=1e-2, warmup_steps=2, total_steps=50,
                    weight_decay=0.0, **opt_kw)
    state = init_state(model, opt, jax.random.PRNGKey(0))
    step = jax.jit(build_train_step(model, opt, microbatches=microbatches))
    ds = DataConfig(vocab=CFG.vocab, seq_len=32, global_batch=8)
    return model, opt, state, step, TokenStream(ds)


def test_loss_decreases():
    _, _, state, step, stream = small_setup()
    losses = []
    for i in range(25):
        state, m = step(state, stream.batch_at(i))
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 0.2


def test_microbatch_equivalence():
    """Grad accumulation over 4 microbatches == single big batch."""
    _, _, s1, step1, stream = small_setup(microbatches=1)
    _, _, s4, step4, _ = small_setup(microbatches=4)
    b = stream.batch_at(0)
    n1, m1 = step1(s1, b)
    n4, m4 = step4(s4, b)
    np.testing.assert_allclose(float(m1["loss"]), float(m4["loss"]),
                               rtol=1e-5)
    for a, c in zip(jax.tree.leaves(n1["params"]),
                    jax.tree.leaves(n4["params"])):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(c, np.float32),
                                   rtol=2e-2, atol=2e-5)


def test_lr_schedule():
    opt = OptConfig(lr=1.0, warmup_steps=10, total_steps=110,
                    schedule="cosine")
    assert float(lr_at(opt, 0)) == 0.0
    assert float(lr_at(opt, 10)) == pytest.approx(1.0)
    assert float(lr_at(opt, 110)) == pytest.approx(0.0, abs=1e-6)
    assert 0.4 < float(lr_at(opt, 60)) < 0.6


def test_quantize_roundtrip():
    x = jnp.array(np.random.default_rng(0).standard_normal(1000),
                  jnp.float32)
    q, s = quantize_int8(x, block=128)
    y = dequantize_int8(q, s, x.shape, block=128)
    err = np.abs(np.array(x) - np.array(y)).max()
    scale = np.abs(np.array(x)).max()
    assert err <= scale / 127.0 + 1e-6


def test_compression_error_feedback_converges():
    """int8-compressed training still reduces the loss; error feedback
    keeps the accumulated quantization bias bounded."""
    _, _, state, step, stream = small_setup(compress_int8=True)
    losses = []
    for i in range(25):
        state, m = step(state, stream.batch_at(i))
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 0.2
    err_norm = sum(float(jnp.sum(jnp.abs(e)))
                   for e in jax.tree.leaves(state["opt"]["err"]))
    assert np.isfinite(err_norm)


def test_state_int8_converges_and_shrinks():
    """8-bit Adam states: loss still decreases; state bytes ~4x smaller."""
    _, _, s32, step32, stream = small_setup()
    _, _, s8, step8, _ = small_setup(state_int8=True)
    b32 = sum(x.nbytes for x in jax.tree.leaves(s32["opt"]["m"]))
    b8 = sum(x.nbytes for x in jax.tree.leaves(s8["opt"]["m"]))
    assert b8 < b32 / 3
    losses = []
    for i in range(25):
        s8, m = step8(s8, stream.batch_at(i))
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 0.2


def test_compressed_psum_matches_psum():
    from jax.sharding import Mesh, PartitionSpec as P
    from repro.train.optimizer import compressed_psum

    mesh = Mesh(np.array(jax.devices()[:1]), ("d",))
    x = jnp.linspace(-1, 1, 256)

    def f(x):
        return compressed_psum(x, "d")

    y = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P(None),
                              out_specs=P(None)))(x)
    np.testing.assert_allclose(np.array(y), np.array(x), atol=1e-2)


# ---------------------------------------------------------------------------
# checkpoint
# ---------------------------------------------------------------------------

def test_ckpt_roundtrip_and_keep(tmp_path):
    tree = {"a": jnp.arange(10, dtype=jnp.float32),
            "b": {"c": jnp.ones((3, 4), jnp.bfloat16)}}
    d = str(tmp_path)
    for s in (1, 2, 3, 4):
        ckpt.save(d, s, tree, extras={"cursor": {"step": s}}, keep=2)
    assert ckpt.committed_steps(d) == [3, 4]
    out, step, extras = ckpt.restore(d, tree)
    assert step == 4 and extras["cursor"]["step"] == 4
    np.testing.assert_array_equal(np.asarray(out["a"]), np.arange(10))


def test_ckpt_ignores_uncommitted(tmp_path):
    tree = {"a": jnp.arange(4)}
    d = str(tmp_path)
    ckpt.save(d, 1, tree)
    # simulate a crash mid-write: directory without .done marker
    os.makedirs(os.path.join(d, "step_000000099"))
    assert ckpt.latest_step(d) == 1


def test_ckpt_shape_mismatch_rejected(tmp_path):
    d = str(tmp_path)
    ckpt.save(d, 1, {"a": jnp.zeros((4,))})
    with pytest.raises(ValueError):
        ckpt.restore(d, {"a": jnp.zeros((5,))})


def test_elastic_remesh_restore(tmp_path):
    """Restore a checkpoint onto a different sharding layout."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    d = str(tmp_path)
    tree = {"w": jnp.arange(16, dtype=jnp.float32).reshape(4, 4)}
    ckpt.save(d, 5, tree)
    mesh = jax.make_mesh((1,), ("data",))
    sh = {"w": NamedSharding(mesh, P("data", None))}
    out, step, _ = ckpt.restore(d, tree, shardings=sh)
    assert out["w"].sharding == sh["w"]
    np.testing.assert_array_equal(np.asarray(out["w"]),
                                  np.asarray(tree["w"]))


def test_loop_restart_continues(tmp_path):
    model, opt, state, step, stream = small_setup()
    lc = LoopConfig(total_steps=6, ckpt_dir=str(tmp_path), ckpt_every=3,
                    log_every=100)
    state1, ls1 = run(lc, state=state, train_step=step, stream=stream,
                      log=lambda *a: None)
    assert ls1.step == 6
    # fresh state; loop must resume from step 6 and do nothing more
    lc2 = LoopConfig(total_steps=6, ckpt_dir=str(tmp_path), ckpt_every=3,
                     log_every=100)
    model2, opt2, state2, step2, stream2 = small_setup()
    state2b, ls2 = run(lc2, state=state2, train_step=step2, stream=stream2,
                       log=lambda *a: None)
    assert ls2.step == 6
    for a, b in zip(jax.tree.leaves(state1["params"]),
                    jax.tree.leaves(state2b["params"])):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


# ---------------------------------------------------------------------------
# data pipeline
# ---------------------------------------------------------------------------

def test_data_deterministic():
    dc = DataConfig(vocab=100, seq_len=64, global_batch=4, seed=3)
    a = TokenStream(dc).batch_at(7)
    b = TokenStream(dc).batch_at(7)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])


def test_data_shards_partition_global_batch():
    dc = DataConfig(vocab=100, seq_len=32, global_batch=8, seed=1)
    full = TokenStream(dc).batch_at(3)["tokens"]
    parts = []
    for sid in range(4):
        dcs = DataConfig(vocab=100, seq_len=32, global_batch=8, seed=1,
                         n_shards=4, shard_id=sid)
        parts.append(TokenStream(dcs).batch_at(3)["tokens"])
    np.testing.assert_array_equal(np.concatenate(parts, 0), full)


def test_data_labels_shifted():
    dc = DataConfig(vocab=100, seq_len=32, global_batch=2)
    b = TokenStream(dc).batch_at(0)
    np.testing.assert_array_equal(b["labels"][:, :-1], b["tokens"][:, 1:])
    assert (b["labels"][:, -1] == -1).all()


def test_data_resume_cursor():
    dc = DataConfig(vocab=100, seq_len=32, global_batch=2)
    s = TokenStream(dc)
    next(s)
    next(s)
    s2 = TokenStream.from_cursor(dc, s.cursor())
    np.testing.assert_array_equal(next(s)["tokens"], next(s2)["tokens"])
