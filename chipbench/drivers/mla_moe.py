"""Driver of DeepSeek-V2-type configurations (multi-head latent attention,
a dense first layer, then MoE layers of routed and shared experts):
``repro.serve.Engine`` serving the model at its published widths, holding
the configuration's share of the routed experts, with bf16 weights the
benchmark makes from the seed, checked against the plain reference.

The traffic kind, its window and the check are those of the ``lm`` driver
(``offline_waves``; the widest gap by which a served token's reference
logit lies below the reference's best at its position, held to the
configuration's limit), whose functions this driver reuses; what differs
is the program's configuration, the weights and the reference.
"""
from __future__ import annotations

from pathlib import Path

from chipbench import harness

lm = harness.load_module(Path(__file__).with_name("lm.py"),
                         "chipbench_driver_lm_for_mla_moe")
prompts, window = lm.prompts, lm.window
counts, release, sample = lm.counts, lm.release, lm.sample
WARM, TRACE = lm.WARM, lm.TRACE
#: decode steps of the traced slice: a whole wave runs ~2,100 device
#: operations a step, ~530k a wave, past the 400k a chip's line may hold
#: in the trace's reduction (``trace.MAX_OPS``), which then keeps module
#: executions only and the flash kernel's calls go unread
TRACED_STEPS = 128


def program_config(c: dict):
    """The program's model config named by ``serving.program_config``, at
    the sizes, expert share and precision configuration file ``c`` states;
    the architecture it implies is checked against the file."""
    from repro.configs import get_config
    srv, y = c["serving"], c["rope_scaling"]
    cfg = get_config(srv["program_config"]).replace(
        d_model=c["hidden_size"], d_ff=c["intermediate_size"],
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"],
        n_layers=c["num_hidden_layers"], vocab_size=c["vocab_size"],
        tie_embeddings=c["tie_word_embeddings"], head_dim=0,
        norm_eps=float(c["rms_norm_eps"]), rope_theta=float(c["rope_theta"]),
        kv_lora_rank=c["kv_lora_rank"],
        qk_nope_head_dim=c["qk_nope_head_dim"],
        qk_rope_head_dim=c["qk_rope_head_dim"], v_head_dim=c["v_head_dim"],
        n_experts=c["n_routed_experts_published"],
        experts_held=c["n_routed_experts"], expert_shard=c["expert_shard"],
        topk=c["num_experts_per_tok"],
        moe_d_ff=c["moe_intermediate_size"],
        n_shared_experts=c["n_shared_experts"],
        first_k_dense=c["first_k_dense_replace"],
        norm_topk_prob=c["norm_topk_prob"],
        routed_scaling=float(c["routed_scaling_factor"]),
        yarn_factor=float(y["factor"]),
        yarn_original_max_pos=y["original_max_position_embeddings"],
        yarn_beta_fast=float(y["beta_fast"]),
        yarn_beta_slow=float(y["beta_slow"]),
        yarn_mscale=float(y["mscale"]),
        yarn_mscale_all_dim=float(y["mscale_all_dim"]),
        use_pallas=srv["use_pallas"], compute_dtype=srv["compute_dtype"],
        param_dtype=srv["param_dtype"])
    want = {"mlp": {"silu": "swiglu"}[c["hidden_act"]], "norm": "rmsnorm",
            "pattern_unit": ("mla",), "window": 0, "causal": True,
            "mrope": False, "frontend": None, "scan_layers": True,
            "attn_bias": c["attention_bias"]}
    got = {k: getattr(cfg, k) for k in want}
    if got != want or c["q_lora_rank"] is not None \
            or c["scoring_func"] != "softmax" \
            or c["topk_method"] != "greedy" or c["moe_layer_freq"] != 1 \
            or y["type"] != "yarn":
        raise ValueError(f"program config {srv['program_config']!r} is not "
                         f"the file's architecture: {got} != {want}")
    return cfg


def make_params(c: dict, seed: int):
    """The program's parameter tree, made on the device in one jitted call
    from ``seed`` in the serving dtype: linear weights normal with std
    ``1/sqrt(fan_in)``, the router's ``init.router_gain`` times that, the
    embedding with std ``init.embed_std``, norm scales ``1 +
    init.norm_jitter * normal``."""
    import jax
    import jax.numpy as jnp

    d, V = c["hidden_size"], c["vocab_size"]
    H, r = c["num_attention_heads"], c["kv_lora_rank"]
    nope, rope = c["qk_nope_head_dim"], c["qk_rope_head_dim"]
    vd, ff = c["v_head_dim"], c["intermediate_size"]
    mff = c["moe_intermediate_size"]
    sff = c["n_shared_experts"] * mff
    E, held = c["n_routed_experts_published"], c["n_routed_experts"]
    Ld = c["first_k_dense_replace"]
    Lm = c["num_hidden_layers"] - Ld
    ini = c["init"]
    dt = jnp.dtype(c["serving"]["param_dtype"])

    def make(key):
        ks = iter(jax.random.split(key, 64))

        def normal(shape, std, mean=0.0):
            return (mean + std * jax.random.normal(next(ks), shape,
                                                   jnp.float32)).astype(dt)

        def lin(shape):
            return {"w": normal(shape, shape[-2] ** -0.5)}

        def norm(shape):
            return {"scale": normal(shape, ini["norm_jitter"], 1.0)}

        def mla(L):
            return {"norm": norm((L, d)), "wq": lin((L, d, H * (nope + rope))),
                    "wkv_a": lin((L, d, r + rope)), "kv_norm": norm((L, r)),
                    "wkv_b": lin((L, r, H * (nope + vd))),
                    "wo": lin((L, H * vd, d))}

        dense = {"mixer": mla(Ld),
                 "mlp": {"norm": norm((Ld, d)), "wi": lin((Ld, d, 2 * ff)),
                         "wo": lin((Ld, ff, d))}}
        moe = {"mixer": mla(Lm),
               "mlp": {"norm": norm((Lm, d)),
                       "router": {"w": normal((Lm, d, E),
                                              ini["router_gain"] * d ** -0.5)},
                       "wi": normal((Lm, held, d, 2 * mff), d ** -0.5),
                       "wo": normal((Lm, held, mff, d), mff ** -0.5),
                       "shared": {"wi": lin((Lm, d, 2 * sff)),
                                  "wo": lin((Lm, sff, d))}}}
        return {"embed": {"w": normal((V, d), ini["embed_std"])},
                "groups": {"0": {"0": dense}, "1": {"0": moe}},
                "final_norm": norm((d,)),
                "lm_head": lin((d, V))}

    key = jax.random.PRNGKey(seed % 2**32)
    key = jax.random.fold_in(key, seed // 2**32)
    return jax.jit(make)(key)


class State:
    pass


def setup(run):
    import jax
    from repro.models.schema import abstract_params
    from repro.serve import Engine, EngineConfig

    if run.traffic["kind"] != "offline_waves":
        raise ValueError(f"unknown traffic kind {run.traffic['kind']!r}")
    st = State()
    st.cfg = program_config(run.config)
    with run.span("weights"):
        st.params = make_params(run.config, run.seed)
        jax.block_until_ready(st.params)
    want = jax.tree.map(lambda a: (a.shape, str(a.dtype)),
                        abstract_params(st.cfg))
    got = jax.tree.map(lambda a: (a.shape, str(a.dtype)), st.params)
    if want != got:
        raise ValueError("the parameter tree made here is not the "
                         "program's layout")
    t = run.traffic
    st.engine = Engine(st.cfg, st.params,
                       EngineConfig(slots=t["slots"], temperature=0.0))
    with run.span("warmup"):
        st.engine.generate(prompts(run, WARM, 0), max_new=t["max_new"])
    st.waves = []
    return st


def traced_slice(run, st) -> None:
    """What a traced run traces, after the window: one more wave's prefill
    and its first ``TRACED_STEPS`` greedy decode steps, through the
    engine's own prefill and jitted decode step at the wave's shapes (the
    cache's capacity is that of the whole wave), each step's tokens pulled
    one step behind as the engine does."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models import model as M

    eng = st.engine
    batch = np.asarray(prompts(run, TRACE, 0), np.int32)
    plen = batch.shape[1]
    logits, cache = M.prefill(eng.params, eng.cfg, tokens=jnp.asarray(batch),
                              pad_to=plen + run.traffic["max_new"] + 1)
    last = jnp.argmax(logits, axis=-1)
    for t in range(min(TRACED_STEPS, run.traffic["max_new"] - 1)):
        logits, cache = eng.decode_fn(eng.params, cache, last[:, None],
                                      jnp.asarray(plen + t, jnp.int32))
        nxt = jnp.argmax(logits, axis=-1)
        jax.device_get(last)
        last = nxt
    jax.device_get(last)


def gaps(run, st, fp8: bool = False):
    """Per sampled request: (served gaps, control gaps or None)."""
    import numpy as np

    ref = run.spec.reference(run.config["reference"])
    w = ref.from_program(st.params)
    s = ref.Sizes.of(run.config)
    out = []
    for p, o in sample(run, st):
        g, c = ref.served_gaps(w, s, p, o[len(p):], fp8=fp8)
        out.append((np.asarray(g), None if c is None else np.asarray(c)))
    return out


def check(run, st) -> list:
    limits = run.config["limits"]
    widest = max(float(g.max()) for g, _ in gaps(run, st))
    return [
        {"name": "requests_malformed", "value": lm._malformed(run, st),
         "limit": limits["requests_malformed"]},
        {"name": "served_logit_gap", "value": widest,
         "limit": limits["served_logit_gap"]},
    ]
