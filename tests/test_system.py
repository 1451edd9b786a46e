"""End-to-end behaviour tests: every assigned architecture trains a step on
a reduced config (CPU), serving is consistent with training-mode forward,
and the fault-tolerance loop resumes bit-identically."""
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config, get_smoke
from repro.data.pipeline import DataConfig
from repro.models import model as M
from repro.models.config import SHAPES, cell_supported
from repro.models.schema import count_params, init_params
from repro.models.steps import make_decode_step, make_train_step
from repro.optim import adamw
from repro.train.trainer import Trainer, TrainConfig

RNG = jax.random.PRNGKey(0)


def _batch(cfg, b=2, s=32):
    if cfg.frontend:
        out = {"embeds": jax.random.normal(RNG, (b, s, cfg.d_frontend)),
               "labels": jax.random.randint(RNG, (b, s), 0, cfg.vocab_size)}
        if cfg.mrope:
            out["positions"] = jnp.broadcast_to(
                jnp.arange(s)[None, None, :], (3, b, s))
        return out
    return {"tokens": jax.random.randint(RNG, (b, s + 1), 0, cfg.vocab_size)}


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_train_step(arch):
    """One train step on the reduced config: finite loss, params update,
    correct output structure."""
    cfg = get_smoke(arch)
    params = init_params(cfg, RNG)
    hp = adamw.AdamWConfig(warmup_steps=2, total_steps=10)
    step = jax.jit(make_train_step(cfg, hp))
    opt = adamw.init(params)
    batch = _batch(cfg)
    p2, o2, metrics = step(params, opt, batch)
    assert jnp.isfinite(metrics["loss"]), f"{arch}: NaN loss"
    assert float(metrics["loss"]) > 0
    moved = any(
        float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)))) > 0
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(p2)))
    assert moved, f"{arch}: no parameter update"
    assert int(o2.step) == 1


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_forward_shapes(arch):
    cfg = get_smoke(arch)
    params = init_params(cfg, RNG)
    b, s = 2, 16
    if cfg.frontend:
        logits = M.encode(params, cfg, jax.random.normal(
            RNG, (b, s, cfg.d_frontend)))
    else:
        x, _, _ = M.forward(params, cfg, tokens=jnp.zeros((b, s), jnp.int32))
        logits = M.lm_logits(params, cfg, x)
    assert logits.shape == (b, s, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits)))


DECODE_ARCHS = [a for a in ARCH_IDS
                if not get_smoke(a).is_encoder_only
                and get_smoke(a).frontend is None]


@pytest.mark.parametrize("steps", [1, 6])
@pytest.mark.parametrize("arch", DECODE_ARCHS)
def test_prefill_decode_consistency(arch, steps):
    """Gold test: after prefill(S - steps), each of ``steps`` decode steps
    (the engine's jitted step, the cache donated and written in place)
    gives the full forward's logits at its position. Covers the ring
    buffer of the windowed kinds wrapping (S - steps is past the smoke
    window of 16), the recurrent states and MLA's latent cache."""
    cfg = get_smoke(arch)
    if cfg.n_experts:
        cfg = cfg.replace(capacity_factor=float(cfg.n_experts))  # no drops
    params = init_params(cfg, jax.random.PRNGKey(1))
    b, s = 2, 24
    tokens = jax.random.randint(jax.random.PRNGKey(2), (b, s), 0,
                                cfg.vocab_size)
    x, _, _ = M.forward(params, cfg, tokens=tokens)
    ref = M.lm_logits(params, cfg, x)
    _, cache = M.prefill(params, cfg, tokens=tokens[:, :s - steps],
                         pad_to=s + 4)
    step = jax.jit(make_decode_step(cfg), donate_argnums=(1,))
    for t in range(s - steps, s):
        dec_logits, cache = step(params, cache, tokens[:, t:t + 1],
                                 jnp.array(t, jnp.int32))
        err = float(jnp.max(jnp.abs(ref[:, t] - dec_logits)))
        scale = float(jnp.max(jnp.abs(ref[:, t])))
        assert err / max(scale, 1e-9) < 0.05, \
            f"{arch}: decode diverges at position {t} ({err})"


def test_full_configs_match_spec():
    """The full (dry-run) configs carry the exact published dimensions."""
    spec = {
        "llama4-scout-17b-a16e": (48, 5120, 40, 8, 8192, 202048),
        "mixtral-8x7b": (32, 4096, 32, 8, 14336, 32000),
        "xlstm-350m": (24, 1024, 4, 4, 0, 50304),
        "qwen1.5-4b": (40, 2560, 20, 20, 6912, 151936),
        "granite-8b": (36, 4096, 32, 8, 14336, 49152),
        "qwen1.5-0.5b": (24, 1024, 16, 16, 2816, 151936),
        "smollm-360m": (32, 960, 15, 5, 2560, 49152),
        "recurrentgemma-2b": (26, 2560, 10, 1, 7680, 256000),
        "hubert-xlarge": (48, 1280, 16, 16, 5120, 504),
        "qwen2-vl-72b": (80, 8192, 64, 8, 29568, 152064),
        "deepseek-v2-lite": (27, 2048, 16, 16, 10944, 102400),
    }
    for arch, (l, d, h, kv, ff, v) in spec.items():
        c = get_config(arch)
        assert (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads, c.d_ff,
                c.vocab_size) == (l, d, h, kv, ff, v), arch


def test_cell_support_matrix():
    """44 cells; the documented 9 skips and 35 live cells."""
    live = skips = 0
    for a in ARCH_IDS:
        for s in SHAPES.values():
            ok, reason = cell_supported(get_config(a), s)
            live += ok
            skips += not ok
            if not ok:
                assert reason
    assert live == 35 and skips == 9


def test_trainer_resume_bit_identical(tmp_path):
    cfg = get_smoke("qwen1.5-0.5b")
    hp = adamw.AdamWConfig(lr=1e-2, warmup_steps=2, total_steps=12)
    dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=32, global_batch=4)
    a_dir, b_dir = tmp_path / "a", tmp_path / "b"
    r1 = Trainer(cfg, hp, TrainConfig(steps=8, save_every=4,
                                      ckpt_dir=str(a_dir)), dc).run()
    with pytest.raises(RuntimeError):
        Trainer(cfg, hp, TrainConfig(steps=8, save_every=4,
                                     ckpt_dir=str(b_dir), fail_at_step=6),
                dc).run()
    r2 = Trainer(cfg, hp, TrainConfig(steps=8, save_every=4,
                                      ckpt_dir=str(b_dir)), dc).run()
    for x, y in zip(jax.tree.leaves(r1["params"]),
                    jax.tree.leaves(r2["params"])):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_training_loss_decreases(tmp_path):
    cfg = get_smoke("smollm-360m")
    hp = adamw.AdamWConfig(lr=1e-2, warmup_steps=3, total_steps=25)
    dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=64, global_batch=8)
    t = Trainer(cfg, hp, TrainConfig(steps=20, save_every=20,
                                     ckpt_dir=str(tmp_path)), dc)
    t.run()
    losses = [m["loss"] for m in t.metrics_log]
    assert losses[-1] < losses[0] * 0.9


def test_param_counts_reasonable():
    """Full-config parameter counts land near the published sizes."""
    approx = {"mixtral-8x7b": 46.7e9, "granite-8b": 8.1e9,
              "qwen1.5-0.5b": 0.62e9, "smollm-360m": 0.36e9,
              "recurrentgemma-2b": 2.7e9, "qwen2-vl-72b": 72.7e9,
              "xlstm-350m": 0.35e9, "deepseek-v2-lite": 15.7e9}
    for arch, expect in approx.items():
        n = count_params(get_config(arch))
        assert 0.6 * expect < n < 1.55 * expect, (arch, n, expect)
