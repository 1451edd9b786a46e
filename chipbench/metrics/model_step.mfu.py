"""model_step.mfu: the model FLOPs of every prefill and decode token of the
waves completed in the window, counted from the configuration's shapes,
over the window's length times the chip's bf16 peak, in percent. A traced
run's window runs untraced (its trace is of a wave after it), so this is
the share ``tokens_per_s`` implies, with no profiler in it.

Counted per wave of ``B`` prompts of ``S`` tokens with ``N`` new tokens
(``N - 1`` decode steps; the first new token comes from prefill):

- matrix products: ``2 * P`` per token and layer, ``P = d*H*hd + 2*d*K*hd
  + H*hd*d + 3*d*ff`` (q, k, v, o, gate, up, down), for ``B*S`` prefill
  and ``B*(N-1)`` decode tokens;
- attention: ``4 * H * hd`` per (query, key) pair a causal model needs, per
  layer: ``S*(S+1)/2`` pairs per prompt in prefill, ``S + t + 1`` at
  decode step ``t``;
- logits: ``2 * d * V`` for the last prompt position and each decode token.
"""


def wave_flops(c: dict, t: dict) -> float:
    d, ff = c["hidden_size"], c["intermediate_size"]
    H, K = c["num_attention_heads"], c["num_key_value_heads"]
    L, V = c["num_hidden_layers"], c["vocab_size"]
    hd = d // H
    B, S, N = t["prompts"], t["prompt_len"], t["max_new"]
    P = d * H * hd + 2 * d * K * hd + H * hd * d + 3 * d * ff
    dec = N - 1
    matmul = 2 * P * L * B * (S + dec)
    pairs = S * (S + 1) // 2 + sum(S + i + 1 for i in range(dec))
    attn = 4 * H * hd * L * B * pairs
    logits = 2 * d * V * B * (1 + dec)
    return float(matmul + attn + logits)


def read(run):
    waves = run.records.get("waves")
    if not waves:
        return None
    t0, t1 = run.window
    flops = waves * wave_flops(run.config, run.traffic)
    peak = run.peaks()["bf16_flops_per_s"] * len(run.devices)
    return 100.0 * flops / (t1 - t0) / peak
