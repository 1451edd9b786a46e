"""DeepSeek-V2-Lite's block on the CPU at SMOKE size, against the plain
reference (``repro.models.reference``) on seeded random weights: prefill and
decoding through the latent cache as logits, the expert share against the
uncut MoE layer, dropless routing, YaRN at the published parameters, the
parameter count, and the flash kernel's narrower value heads."""
from __future__ import annotations

import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, get_smoke
from repro.kernels.flash_attention import flash_attention
from repro.models import layers, mla, moe
from repro.models import model as M
from repro.models import reference as R
from repro.models.attention import blocked_attention
from repro.models.schema import count_params, init_params
from repro.serve import Engine, EngineConfig

SMOKE = get_smoke("deepseek-v2-lite")


def hf_keys(cfg) -> dict:
    """The reference's configuration keys (Hugging Face names) of ``cfg``."""
    return {
        "hidden_size": cfg.d_model, "num_attention_heads": cfg.n_heads,
        "kv_lora_rank": cfg.kv_lora_rank,
        "qk_nope_head_dim": cfg.qk_nope_head_dim,
        "qk_rope_head_dim": cfg.qk_rope_head_dim,
        "v_head_dim": cfg.v_head_dim, "intermediate_size": cfg.d_ff,
        "moe_intermediate_size": cfg.moe_ff,
        "n_shared_experts": cfg.n_shared_experts,
        "n_routed_experts": cfg.n_experts_held,
        "n_routed_experts_published": cfg.n_experts,
        "expert_shard": cfg.expert_shard, "num_experts_per_tok": cfg.topk,
        "norm_topk_prob": cfg.norm_topk_prob,
        "routed_scaling_factor": cfg.routed_scaling,
        "num_hidden_layers": cfg.n_layers,
        "first_k_dense_replace": cfg.first_k_dense,
        "vocab_size": cfg.vocab_size, "rms_norm_eps": cfg.norm_eps,
        "rope_theta": cfg.rope_theta,
        "rope_scaling": {
            "type": "yarn", "factor": cfg.yarn_factor,
            "original_max_position_embeddings": cfg.yarn_original_max_pos,
            "beta_fast": cfg.yarn_beta_fast, "beta_slow": cfg.yarn_beta_slow,
            "mscale": cfg.yarn_mscale,
            "mscale_all_dim": cfg.yarn_mscale_all_dim}}


def _served_logits(cfg, params, tokens, prompt_len):
    """Prefill ``prompt_len`` tokens, then decode the rest one at a time
    through the latent cache; logits at every position from the last
    prompt one onward."""
    b, s = tokens.shape
    last, cache = M.prefill(params, cfg, tokens=tokens[:, :prompt_len],
                            pad_to=s + 1)
    out = [last]
    step = jax.jit(lambda p, c, t, i: M.decode_step(p, cfg, c, t, i))
    for t in range(prompt_len, s):
        lg, cache = step(params, cache, tokens[:, t:t + 1],
                         jnp.asarray(t, jnp.int32))
        out.append(lg)
    return jnp.stack(out, 1)


def _setup(cfg, seed=1, b=2, s=40):
    params = init_params(cfg, jax.random.PRNGKey(seed))
    tokens = jax.random.randint(jax.random.PRNGKey(seed + 100), (b, s), 0,
                                cfg.vocab_size)
    return params, tokens


def _ref_logits(cfg, params, tokens, first):
    s = R.Sizes.of(hf_keys(cfg))
    w = R.from_program(params)
    return jnp.stack([R.logits(w, s, row, first) for row in tokens])


# -- prefill + decode against the reference ----------------------------------

def test_f32_prefill_then_latent_decode_matches_the_reference():
    """In f32 compute the served path is the reference's arithmetic up to
    summation order: prefill decompressed, decode absorbed."""
    cfg = SMOKE.replace(compute_dtype="float32")
    params, tokens = _setup(cfg)
    p = 24                                   # past YaRN's original 16
    with jax.default_matmul_precision("highest"):
        got = _served_logits(cfg, params, tokens, p)
    want = _ref_logits(cfg, params, tokens, p - 1)
    err = float(jnp.max(jnp.abs(got - want)))
    assert err < 1e-4 * float(jnp.max(jnp.abs(want))), err


def _bf16_gap(fp8: bool):
    """Widest gap |served - reference| of bf16 serving, and of the float8
    control (the reference with float8 matmul operands), in logits."""
    cfg = SMOKE.replace(param_dtype="bfloat16")
    params, tokens = _setup(cfg, seed=3)
    p = 24
    want = _ref_logits(cfg, params, tokens, p - 1)
    if fp8:
        s = R.Sizes.of(hf_keys(cfg))
        w = R.from_program(params)
        got = jnp.stack([R.logits(w, s, row, p - 1, fp8=True)
                         for row in tokens])
    else:
        got = _served_logits(cfg, params, tokens, p).astype(jnp.float32)
    return float(jnp.max(jnp.abs(got - want)))


# bf16 weights, activations and latent cache: over seeds 3-8 the served
# logits lie 0.06-0.35 from the reference's (max |logit| ~ 4), the widest
# where bf16 rounding flips a near-tie of the router's top-k; float8
# operands (3 mantissa bits against bf16's 8) miss by 1.0-1.7
BF16_LOGIT_TOL = 0.5


def test_bf16_serving_is_within_the_stated_tolerance():
    assert _bf16_gap(fp8=False) < BF16_LOGIT_TOL


def test_float8_matmul_operands_break_the_bf16_tolerance():
    assert _bf16_gap(fp8=True) > BF16_LOGIT_TOL


def test_engine_serves_the_latent_cache_through_its_normal_path():
    """Engine.generate: prefill, jit(decode_step) over the latent cache,
    greedy tokens equal to the argmax of the step-by-step logits."""
    cfg = SMOKE.replace(compute_dtype="float32")
    params, tokens = _setup(cfg, b=2, s=12)
    eng = Engine(cfg, params, EngineConfig(slots=2))
    outs = eng.generate([list(map(int, r)) for r in tokens], max_new=6)
    seq = jnp.asarray(outs, jnp.int32)
    lg = _served_logits(cfg, params, seq[:, :-1], 12)
    assert (np.asarray(jnp.argmax(lg, -1)) == np.asarray(seq[:, 12:])).all()
    cache = M.init_cache(cfg, 2, 19)
    assert isinstance(cache["1"]["0"], mla.MLACache)
    assert M.cache_capacity(19) == 24        # a multiple of 8 positions
    assert cache["1"]["0"].c_kv.shape == (2, 2, 24, cfg.kv_lora_rank)


def test_decode_step_keeps_no_f32_copy_of_the_latent_cache():
    cfg = get_config("deepseek-v2-lite").replace(
        n_layers=2, experts_held=8, param_dtype="bfloat16")
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    cache = jax.eval_shape(lambda: M.init_cache(cfg, 4, 96))
    text = jax.jit(lambda p, c, t, i: M.decode_step(p, cfg, c, t, i)).lower(
        params, cache, jax.ShapeDtypeStruct((4, 1), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.int32)).as_text()
    assert "tensor<4x96x512xbf16>" in text   # a layer read from the stack
    # no f32 tensor of a layer's latent cache, or of the stack of them
    assert not re.search(r"tensor<[\dx]*4x96x512xf32>", text)


# -- the expert layer ----------------------------------------------------------

def test_expert_shares_add_up_to_the_uncut_moe_layer():
    """Summed over every share, the held experts' parts plus the shared
    experts once equal the reference's layer holding all the experts."""
    full = SMOKE.replace(compute_dtype="float32", experts_held=0,
                         expert_shard=0)
    held = SMOKE.n_experts_held
    params = init_params(full.replace(experts_held=full.n_experts),
                         jax.random.PRNGKey(5))
    mp = jax.tree.map(lambda a: a[0], params["groups"]["1"]["0"]["mlp"])
    h = jax.random.normal(jax.random.PRNGKey(6), (20, full.d_model))
    with jax.default_matmul_precision("highest"):
        gate, idx = moe.route(mp, h, full)
        total = moe._swiglu(h, mp["shared"]["wi"]["w"],
                            mp["shared"]["wo"]["w"], full)
        for shard in range(full.n_experts // held):
            lo = shard * held
            total = total + moe.held_experts_part(
                {"wi": mp["wi"][lo:lo + held], "wo": mp["wo"][lo:lo + held]},
                h, gate, idx,
                full.replace(experts_held=held, expert_shard=shard))
        uncut = R.moe_part(
            R.Sizes.of(dict(hf_keys(full), n_routed_experts=full.n_experts)),
            False, h, jax.tree.map(
                lambda a: a[0], R.from_program(params)["moe"]["mlp"]))
    np.testing.assert_allclose(np.asarray(total), np.asarray(uncut),
                               atol=1e-5)


def test_a_router_sending_every_token_to_one_expert_drops_nothing():
    """Every token routed to the same held expert: all of them get its
    output, where a capacity dispatch would have dropped most."""
    cfg = SMOKE.replace(compute_dtype="float32", topk=1)
    params = init_params(cfg, jax.random.PRNGKey(7))
    mp = jax.tree.map(lambda a: a[0], params["groups"]["1"]["0"]["mlp"])
    target = cfg.expert_shard * cfg.n_experts_held + 2
    router = jnp.zeros_like(mp["router"]["w"])
    mp = dict(mp, router={"w": router.at[:, target].set(50.0)})
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(8),
                                  (3, 40, cfg.d_model))) + 0.1
    hx = layers.apply_norm(mp["norm"], x, cfg).reshape(120, -1)
    gate, idx = moe.route(mp, hx, cfg)
    assert (idx == target).all()
    with jax.default_matmul_precision("highest"):
        got = moe.held_experts_part(mp, hx, gate, idx, cfg)
        wi, wo = mp["wi"][2], mp["wo"][2]
        g, u = jnp.split(hx @ wi, 2, -1)
        want = gate * ((jax.nn.silu(g) * u) @ wo)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    assert bool(jnp.all(jnp.any(got != 0, -1)))


def test_tokens_routed_only_to_absent_experts_get_nothing_routed():
    cfg = SMOKE.replace(compute_dtype="float32", topk=1)
    params = init_params(cfg, jax.random.PRNGKey(9))
    mp = jax.tree.map(lambda a: a[0], params["groups"]["1"]["0"]["mlp"])
    hx = jax.random.normal(jax.random.PRNGKey(10), (16, cfg.d_model))
    idx = jnp.zeros((16, 1), jnp.int32)      # expert 0: shard 0's, not ours
    got = moe.held_experts_part(mp, hx, jnp.ones((16, 1)), idx, cfg)
    assert float(jnp.max(jnp.abs(got))) == 0.0


# -- YaRN ----------------------------------------------------------------------

def test_yarn_frequencies_and_scale_at_the_published_parameters():
    cfg = get_config("deepseek-v2-lite")
    inv = np.asarray(layers.rope_freqs(cfg, 64), np.float64)
    extra = 10_000.0 ** (-np.arange(0, 64, 2) / 64)

    def d(r):
        return 64 * math.log(4096 / (2 * math.pi * r)) / (2 * math.log(1e4))
    low, high = math.floor(d(32)), math.ceil(d(1))
    assert (low, high) == (10, 23)
    assert np.allclose(inv[:low], extra[:low], rtol=1e-6)        # kept
    assert np.allclose(inv[high:], extra[high:] / 40, rtol=1e-6)  # / factor
    i = 16
    mask = 1 - (i - low) / (high - low)
    assert inv[i] == pytest.approx(extra[i] / 40 * (1 - mask)
                                   + extra[i] * mask, rel=1e-6)
    m = 0.1 * 0.707 * math.log(40) + 1
    assert mla.softmax_scale(cfg) == pytest.approx(192 ** -0.5 * m * m)
    s = R.Sizes.of(hf_keys(cfg))
    assert np.allclose(np.asarray(R.inv_freq(s)), inv, rtol=1e-6)
    assert R.softmax_scale(s) == pytest.approx(mla.softmax_scale(cfg))


# -- parameter counts ----------------------------------------------------------

def test_active_params_count_the_dense_layer_shared_and_routed_width():
    cfg = SMOKE
    d, f, sf = cfg.d_model, cfg.moe_ff, cfg.n_shared_experts * cfg.moe_ff
    attn = (d + d * cfg.n_heads * (16 + 8) + d * (32 + 8) + 32
            + 32 * cfg.n_heads * (16 + 16) + cfg.n_heads * 16 * d)
    dense = d + 3 * d * cfg.d_ff
    moe_fixed = d + d * cfg.n_experts + 3 * d * sf
    routed = 3 * d * f
    embed = 2 * cfg.vocab_size * d + d
    total = 3 * attn + dense + 2 * (moe_fixed + 4 * routed) + embed
    assert count_params(cfg) == total
    # 4 of 16 experts held, top-4: one held expert per token on average
    assert cfg.n_active_params() == total - 2 * 3 * routed
    uncut = cfg.replace(experts_held=0)
    assert uncut.n_active_params() == count_params(uncut) - 2 * 12 * routed


def test_published_size_and_the_chip_share():
    cfg = get_config("deepseek-v2-lite")
    assert 15.0e9 < count_params(cfg) < 16.5e9             # 15.7B
    embed = cfg.vocab_size * cfg.d_model          # one row read per token
    assert 2.35e9 < cfg.n_active_params() - embed < 2.5e9  # A2.4B
    share = cfg.replace(experts_held=8)
    assert count_params(share) == pytest.approx(3.11e9, rel=0.01)


# -- the flash kernel's value width ----------------------------------------------

@pytest.mark.parametrize("hd, hd_v", [(48, 32), (192, 128)])
def test_flash_attention_with_narrower_value_heads(hd, hd_v):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(11), 3)
    b, s, h = 1, 96, 2
    q = jax.random.normal(k1, (b * h, s, hd))
    k = jax.random.normal(k2, (b * h, s, hd))
    v = jax.random.normal(k3, (b * h, s, hd_v))
    out = flash_attention(q, k, v, causal=True, scale=0.1, block_q=32,
                          block_k=32, interpret=True)
    want = blocked_attention(
        q.reshape(b, h, s, hd).transpose(0, 2, 1, 3),
        k.reshape(b, h, s, hd).transpose(0, 2, 1, 3),
        v.reshape(b, h, s, hd_v).transpose(0, 2, 1, 3), causal=True,
        window=0, q_offset=0, chunk_q=32, chunk_kv=32, scale=0.1)
    assert out.shape == (b * h, s, hd_v)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(want.transpose(0, 2, 1, 3).reshape(
            b * h, s, hd_v)), atol=2e-5)


def test_flash_attention_equal_value_heads_unchanged():
    """hd_v == hd gives what the kernel gave before values had a width of
    their own: the same program, so the same bits."""
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(12), 3)
    q = jax.random.normal(k1, (4, 64, 32))
    k = jax.random.normal(k2, (2, 64, 32))
    v = jax.random.normal(k3, (2, 64, 32))
    out = flash_attention(q, k, v, causal=True, block_q=32, block_k=32,
                          interpret=True)
    text = flash_attention.lower(q, k, v, causal=True, block_q=32,
                                 block_k=32, interpret=True).as_text()
    assert "64x32" in text and "x192" not in text
    from repro.kernels import ref
    want = ref.attention_ref(q, k, v, causal=True, window=0,
                             scale=32 ** -0.5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5)
