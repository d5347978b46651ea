"""Per-architecture smoke tests (deliverable f): reduced config of the same
family, one forward/train step on CPU, output shapes + no NaNs; plus
prefill->decode consistency and recurrent-vs-step equivalence."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, all_configs, get_config, reduced
from repro.configs.base import SMOKE_SHAPE, ShapeConfig
from repro.models import transformer as tf

RNG = np.random.default_rng(0)
B, S = 2, 32


def make_batch(cfg):
    T = S - cfg.prefix_len
    toks = RNG.integers(0, cfg.vocab, size=(B, T)).astype(np.int32)
    batch = {"tokens": jnp.asarray(toks), "labels": jnp.asarray(toks)}
    if cfg.prefix_len:
        batch["prefix_embeds"] = jnp.asarray(
            RNG.normal(size=(B, cfg.prefix_len, cfg.d_model)), jnp.float32)
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS + ["llama-7b"])
def test_smoke_forward_and_train_step(arch):
    cfg = reduced(get_config(arch))
    params = tf.init_params(cfg, jax.random.PRNGKey(0))
    batch = make_batch(cfg)

    logits, _, aux = tf.forward(params, batch["tokens"], cfg,
                                prefix_embeds=batch.get("prefix_embeds"))
    assert logits.shape == (B, S, cfg.vocab_padded)
    assert np.all(np.isfinite(np.asarray(logits, np.float32)))

    loss, metrics = tf.loss_fn(params, batch, cfg)
    assert np.isfinite(float(loss))
    # random-init loss should be near ln(vocab)
    assert float(metrics["ce"]) < np.log(cfg.vocab) + 2.0

    # one gradient step decreases nothing catastrophic (finite grads)
    grads = jax.grad(lambda p: tf.loss_fn(p, batch, cfg)[0])(params)
    for leaf in jax.tree.leaves(grads):
        assert np.all(np.isfinite(np.asarray(leaf, np.float32)))


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_decode_step(arch):
    cfg = reduced(get_config(arch))
    params = tf.init_params(cfg, jax.random.PRNGKey(0))
    kv_len = cfg.window if cfg.window else 16
    caches = tf.init_caches(cfg, B, kv_len)
    tok = jnp.asarray(RNG.integers(0, cfg.vocab, size=(B, 1)).astype(np.int32))
    logits, caches2 = tf.decode_step(params, tok, caches, jnp.int32(0), cfg)
    assert logits.shape == (B, 1, cfg.vocab_padded)
    assert np.all(np.isfinite(np.asarray(logits, np.float32)))
    # cache structure preserved
    assert jax.tree.structure(caches) == jax.tree.structure(caches2)


@pytest.mark.parametrize("arch", ["yi-9b", "xlstm-125m", "hymba-1.5b",
                                  "mixtral-8x7b"])
def test_prefill_decode_consistency(arch):
    """Teacher-forced decode must reproduce full-forward logits."""
    cfg = reduced(get_config(arch))
    params = tf.init_params(cfg, jax.random.PRNGKey(1))
    T = 8
    toks = RNG.integers(0, cfg.vocab, size=(B, T)).astype(np.int32)
    full_logits, _, _ = tf.forward(params, jnp.asarray(toks), cfg,
                                   remat=False)

    kv_len = cfg.window if cfg.window else T
    caches = tf.init_caches(cfg, B, kv_len)
    outs = []
    for t in range(T):
        lg, caches = tf.decode_step(params, jnp.asarray(toks[:, t:t + 1]),
                                    caches, jnp.int32(t), cfg)
        outs.append(np.asarray(lg[:, 0], np.float32))
    dec = np.stack(outs, axis=1)
    np.testing.assert_allclose(
        dec, np.asarray(full_logits, np.float32), rtol=2e-2, atol=2e-2)


def test_sliding_window_ring_buffer_decode():
    """Decode beyond the window: ring buffer must mask out evicted slots."""
    cfg = reduced(get_config("mixtral-8x7b"))
    assert cfg.window == 16
    params = tf.init_params(cfg, jax.random.PRNGKey(2))
    T = 40  # > 2x window
    toks = RNG.integers(0, cfg.vocab, size=(B, T)).astype(np.int32)
    full_logits, _, _ = tf.forward(params, jnp.asarray(toks), cfg,
                                   remat=False)
    caches = tf.init_caches(cfg, B, cfg.window)
    outs = []
    for t in range(T):
        lg, caches = tf.decode_step(params, jnp.asarray(toks[:, t:t + 1]),
                                    caches, jnp.int32(t), cfg)
        outs.append(np.asarray(lg[:, 0], np.float32))
    dec = np.stack(outs, axis=1)
    np.testing.assert_allclose(
        dec, np.asarray(full_logits, np.float32), rtol=3e-2, atol=3e-2)


def test_abstract_init_matches_real_shapes():
    for arch in ("yi-9b", "mixtral-8x7b", "hymba-1.5b", "xlstm-125m"):
        cfg = reduced(get_config(arch))
        real = tf.init_params(cfg, jax.random.PRNGKey(0))
        abstract = tf.init_params(cfg, abstract=True)
        rs = jax.tree.map(lambda x: (x.shape, str(x.dtype)), real)
        as_ = jax.tree.map(lambda x: (x.shape, str(x.dtype)), abstract)
        assert rs == as_


def test_param_labels_cover_params():
    from repro.models.transformer import param_labels

    for arch in ARCH_IDS:
        cfg = reduced(get_config(arch))
        params = tf.init_params(cfg, abstract=True)
        labels = param_labels(cfg)
        jax.tree.map(lambda sds, lab: None, params, labels)  # same structure
        flat_p = jax.tree.leaves(params)
        flat_l = jax.tree.leaves(labels)
        for sds, lab in zip(flat_p, flat_l):
            assert len(lab.split()) == len(sds.shape), (arch, lab, sds.shape)


@pytest.mark.parametrize("arch,kind", [("hymba-1.5b", "ssm"),
                                       ("xlstm-125m", "mlstm")])
def test_chunked_scan_pads_ragged_lengths(arch, kind):
    """A length that is not a multiple of the scan chunk (200 rows, chunks
    of 64) runs identity steps after its end: outputs and final state equal
    the one-chunk scan of the same 200 rows."""
    from repro.models import ssm, xlstm
    from repro.models.common import ParamFactory

    cfg = reduced(get_config(arch))
    pf = ParamFactory(jax.random.PRNGKey(0), jnp.float32, False)
    if kind == "ssm":
        p, fwd = ssm.init_ssm(pf, cfg), ssm.ssm_forward
    else:
        p, fwd = xlstm.init_mlstm(pf, cfg), xlstm.mlstm_forward
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 200, cfg.d_model))
    y, st = fwd(p, x, cfg, chunk=64)
    y1, st1 = fwd(p, x, cfg, chunk=200)
    assert y.shape == x.shape
    np.testing.assert_allclose(np.asarray(y), np.asarray(y1),
                               rtol=1e-4, atol=1e-5)
    for a, b in zip(st, st1):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-6)
