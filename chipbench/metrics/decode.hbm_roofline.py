"""decode.hbm_roofline: the least bytes a decode step must move, over the
chip's memory bandwidth, divided by the device time of the engine's jitted
decode step (the ``jit_decode_step`` program) per call, in percent.

The least bytes of a step are every weight held here except the embedding
table (one row of it is read per token), with the held routed experts
counted at the ``held * (1 - (1 - topk/E) ** B)`` of them that a step of
``B`` tokens is expected to touch, plus the live latent cache, ``B *
(S + t + 1) * L * (rank + rope)`` entries at decode step ``t``, averaged over
the ``N - 1`` steps of a wave; weights and cache in the serving dtype.
"""

MODULE = "jit_decode_step"
BYTES = {"bfloat16": 2, "float32": 4}


def step_bytes(c: dict, t: dict) -> float:
    d, H = c["hidden_size"], c["num_attention_heads"]
    r, nope, rope = c["kv_lora_rank"], c["qk_nope_head_dim"], \
        c["qk_rope_head_dim"]
    vd, V = c["v_head_dim"], c["vocab_size"]
    L, Ld = c["num_hidden_layers"], c["first_k_dense_replace"]
    E, held, k = c["n_routed_experts_published"], c["n_routed_experts"], \
        c["num_experts_per_tok"]
    mff = c["moe_intermediate_size"]
    B, S, N = t["slots"], t["prompt_len"], t["max_new"]
    mla = d * H * (nope + rope) + d * (r + rope) + r * H * (nope + vd) \
        + H * vd * d + d + r                               # + the two norms
    dense = 3 * d * c["intermediate_size"] + d
    touched = held * (1 - (1 - k / E) ** B)
    moe = d * E + 3 * d * c["n_shared_experts"] * mff + d \
        + touched * 3 * d * mff
    weights = L * mla + Ld * dense + (L - Ld) * moe + d + d * V
    live = sum(S + i + 1 for i in range(N - 1)) / (N - 1)
    cache = B * live * L * (r + rope)
    return BYTES[c["serving"]["param_dtype"]] * weights \
        + BYTES[c["serving"]["compute_dtype"]] * cache


def read(run):
    if run.trace is None:
        return None
    sec, n = run.trace.seconds(
        "modules", lambda name: name.split("(")[0] == MODULE)
    if not n:
        return None
    least = step_bytes(run.config, run.traffic) \
        / run.peaks()["hbm_bytes_per_s"]
    return 100.0 * least / (sec / n)
