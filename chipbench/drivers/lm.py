"""Driver of language-model configurations: ``repro.serve.Engine`` serving
the model at its published widths, with weights the benchmark makes from
the seed, checked token by token against the plain reference.

Traffic kind ``offline_waves`` (``chipbench/traffic/<name>.json``): a
closed loop of ``Engine.generate`` calls, each on ``prompts`` prompts of
``prompt_len`` seeded tokens with ``max_new`` new tokens, greedy, with
``slots`` decode slots. The window closes when the first call that ends
past ``--seconds`` returns, so it holds whole waves only. A traced run
traces one more wave after the window.

``correct``: once the window has closed and the engine is freed,
``check_requests`` requests drawn from the seed are run through the
reference over prompt and served tokens; the widest gap by which a
served token's reference logit lies below the reference's best at its
position is held to the configuration's limit.
"""
from __future__ import annotations

import time

import numpy as np

WARM, WINDOW, CHECK, TRACE = 1, 2, 3, 4          # streams of one seed


def program_config(c: dict):
    """The program's model config named by ``serving.program_config``, run
    at the sizes and in the precision configuration file ``c`` states;
    the architecture it implies is checked against the file."""
    from repro.configs import get_config
    srv = c["serving"]
    cfg = get_config(srv["program_config"]).replace(
        d_model=c["hidden_size"], d_ff=c["intermediate_size"],
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"],
        n_layers=c["num_hidden_layers"], vocab_size=c["vocab_size"],
        tie_embeddings=c["tie_word_embeddings"], head_dim=0,
        norm_eps=float(c["rms_norm_eps"]), rope_theta=float(c["rope_theta"]),
        use_pallas=srv["use_pallas"], compute_dtype=srv["compute_dtype"],
        param_dtype=srv["param_dtype"])
    want = {"mlp": {"silu": "swiglu"}[c["hidden_act"]], "norm": "rmsnorm",
            "attn_bias": c["attention_bias"], "pattern_unit": ("attn",),
            "window": 0, "n_experts": 0, "causal": True, "mrope": False,
            "frontend": None, "scan_layers": True}
    got = {k: getattr(cfg, k) for k in want}
    if got != want:
        raise ValueError(f"program config {srv['program_config']!r} is not "
                         f"the file's architecture: {got} != {want}")
    return cfg


def make_params(c: dict, seed: int):
    """The program's parameter tree, made on the device in one jitted call
    from ``seed`` in float32: linear weights normal with std
    ``1/sqrt(fan_in)``, the embedding with std ``init.embed_std``, norm
    scales ``1 + init.norm_jitter * normal``."""
    import jax
    import jax.numpy as jnp

    d, ff = c["hidden_size"], c["intermediate_size"]
    H, K = c["num_attention_heads"], c["num_key_value_heads"]
    L, V = c["num_hidden_layers"], c["vocab_size"]
    hd = d // H
    ini = c["init"]

    def make(key):
        ks = iter(jax.random.split(key, 16))

        def lin(shape):
            return jax.random.normal(next(ks), shape) * shape[-2] ** -0.5

        def norm(shape):
            return 1.0 + ini["norm_jitter"] * jax.random.normal(next(ks),
                                                                shape)
        block = {
            "mixer": {"norm": {"scale": norm((L, d))},
                      "wq": {"w": lin((L, d, H * hd))},
                      "wk": {"w": lin((L, d, K * hd))},
                      "wv": {"w": lin((L, d, K * hd))},
                      "wo": {"w": lin((L, H * hd, d))}},
            "mlp": {"norm": {"scale": norm((L, d))},
                    "wi": {"w": lin((L, d, 2 * ff))},     # gate | up
                    "wo": {"w": lin((L, ff, d))}}}
        return {"embed": {"w": ini["embed_std"]
                          * jax.random.normal(next(ks), (V, d))},
                "groups": {"0": {"0": block}},
                "final_norm": {"scale": norm((d,))}}

    key = jax.random.PRNGKey(seed % 2**32)
    key = jax.random.fold_in(key, seed // 2**32)
    return jax.jit(make)(key)


def reference_weights(ref, params):
    """The reference's layout of the same weights."""
    b = params["groups"]["0"]["0"]
    m, f = b["mixer"], b["mlp"]
    ff = f["wo"]["w"].shape[1]
    return ref.Weights(
        params["embed"]["w"], m["norm"]["scale"], m["wq"]["w"],
        m["wk"]["w"], m["wv"]["w"], m["wo"]["w"], f["norm"]["scale"],
        f["wi"]["w"][..., :ff], f["wi"]["w"][..., ff:], f["wo"]["w"],
        params["final_norm"]["scale"])


def prompts(run, stream: int, j: int):
    t = run.traffic
    rng = np.random.default_rng([run.seed % 2**63, stream, j])
    return rng.integers(0, run.config["vocab_size"],
                        (t["prompts"], t["prompt_len"])).tolist()


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


def _wave(run, st, stream: int, j: int) -> None:
    ps = prompts(run, stream, j)
    with run.span("generate"):
        outs = st.engine.generate(ps, max_new=run.traffic["max_new"])
    st.waves.append((ps, outs))


def window(run, st) -> None:
    t = run.traffic
    t0 = time.perf_counter()
    j = 0
    while True:
        _wave(run, st, WINDOW, j)
        t1 = time.perf_counter()
        j += 1
        if t1 - t0 >= run.seconds:
            break
    run.window = (t0, t1)
    run.records.update(waves=j, new_tokens=j * t["prompts"] * t["max_new"])


def traced_slice(run, st) -> None:
    """What a traced run traces, after the window: one more wave."""
    _wave(run, st, TRACE, 0)


def _malformed(run, st) -> int:
    n = 0
    for ps, outs in st.waves:
        for p, o in zip(ps, outs):
            if len(o) != len(p) + run.traffic["max_new"] or o[:len(p)] != p:
                n += 1
        n += max(0, len(ps) - len(outs))
    return n


def counts(run, st):
    return sum(len(ps) for ps, _ in st.waves), _malformed(run, st)


def release(run, st):
    st.engine = None
    return st


def sample(run, st):
    """``check_requests`` finished requests drawn from the seed."""
    flat = [(p, o) for ps, outs in st.waves for p, o in zip(ps, outs)]
    rng = np.random.default_rng([run.seed % 2**63, CHECK])
    pick = rng.choice(len(flat), size=min(len(flat),
                                          run.traffic["check_requests"]),
                      replace=False)
    return [flat[i] for i in sorted(pick)]


def gaps(run, st, fp8: bool = False):
    """Per sampled request: (served gaps, control gaps or None)."""
    ref = run.spec.reference(run.config["reference"])
    w = reference_weights(ref, st.params)
    s = ref.Sizes.of(run.config)
    out = []
    for p, o in sample(run, st):
        served = o[len(p):]
        g, c = ref.served_gaps(w, s, p, served, fp8=fp8)
        out.append((np.asarray(g), None if c is None else np.asarray(c)))
    return out


def check(run, st) -> list:
    limits = run.config["limits"]
    widest = max(float(g.max()) for g, _ in gaps(run, st))
    return [
        {"name": "requests_malformed", "value": _malformed(run, st),
         "limit": limits["requests_malformed"]},
        {"name": "served_logit_gap", "value": widest,
         "limit": limits["served_logit_gap"]},
    ]
