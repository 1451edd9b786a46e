"""model_step.mfu.mla_moe: the model FLOPs of this chip's share of every
prefill and decode token of the waves completed in the window, counted
from the configuration's shapes, over the window's length times the
chip's bf16 peak, in percent. A traced run's window runs untraced (its
trace is of a wave after it), so this is the share ``tokens_per_s``
implies, with no profiler in it.

Counted per wave of ``B`` prompts of ``S`` tokens with ``N`` new tokens
(``N - 1`` decode steps; the first new token comes from prefill), two
FLOPs per multiply-add:

- MLA projections per token and layer: ``d*H*(nope+rope)`` (q),
  ``d*(rank+rope)`` (joint latent), ``rank*H*(nope+v)`` (k_nope and v; the
  absorbed decode does as many), ``H*v*d`` (out);
- attention: ``2 * (nope + rope + v)`` per (query, key) pair a causal model
  needs, per head and layer: ``S*(S+1)/2`` pairs per prompt in prefill,
  ``S + t + 1`` at decode step ``t``;
- the dense layers: ``3*d*ff`` per token; each MoE layer: the router
  ``d*E``, the shared experts ``3*d*shared_ff``, and the held routed
  experts at the expected ``topk * held / E`` (token, expert) pairs per
  token, ``3*d*moe_ff`` each;
- logits: ``d * V`` for the last prompt position and each decode token.
"""


def wave_flops(c: dict, t: dict) -> float:
    d, H = c["hidden_size"], c["num_attention_heads"]
    r, nope, rope = c["kv_lora_rank"], c["qk_nope_head_dim"], \
        c["qk_rope_head_dim"]
    vd, V = c["v_head_dim"], c["vocab_size"]
    L, Ld = c["num_hidden_layers"], c["first_k_dense_replace"]
    E, held = c["n_routed_experts_published"], c["n_routed_experts"]
    mff = c["moe_intermediate_size"]
    B, S, N = t["prompts"], t["prompt_len"], t["max_new"]
    dec = N - 1
    proj = d * H * (nope + rope) + d * (r + rope) + r * H * (nope + vd) \
        + H * vd * d
    dense = 3 * d * c["intermediate_size"]
    moe = d * E + 3 * d * c["n_shared_experts"] * mff \
        + c["num_experts_per_tok"] * held / E * 3 * d * mff
    per_token = 2 * (L * proj + Ld * dense + (L - Ld) * moe)
    pairs = S * (S + 1) // 2 + sum(S + i + 1 for i in range(dec))
    attn = 2 * (nope + rope + vd) * H * L * B * pairs
    logits = 2 * d * V * B * (1 + dec)
    return float(per_token * B * (S + dec) + attn + logits)


def read(run):
    waves = run.records.get("waves")
    if not waves:
        return None
    t0, t1 = run.window
    flops = waves * wave_flops(run.config, run.traffic)
    peak = run.peaks()["bf16_flops_per_s"] * len(run.devices)
    return 100.0 * flops / (t1 - t0) / peak
