#!/usr/bin/env python3
"""Chip smoke test: the served G-GPU simulator and one model, at real size,
on a TPU, through the entry points a user calls.

    python chip_smoke.py              # phases A and B on one chip
    python chip_smoke.py --chips 4    # only the sharded path, on four chips

Phase A (the system's main path): the eight benches of
``programs.all_benches()`` at their Table III G-GPU sizes on the paper's
largest version (8 CUs), under the ``shared`` and ``banked`` memory
systems. Each bench gets ``LAUNCHES`` seeded memory images, submitted to a
``repro.serve.Scheduler`` and drained (one cohort dispatch per bench); then
a two-config ``Fleet`` (8 CUs and 1 CU) serves the same requests as one
mixed trace. Every ticket must come back, nothing may be quarantined,
every output must equal the bench's NumPy reference, and every launch's
cycles and stats must equal ``PINS``.

Phase B: smollm-360m at its published widths (32 layers, d 960, 15/5
heads, vocab 49,152) with random parameters from ``SEED``, served by
``repro.serve.Engine`` with ``use_pallas=True`` (prefill runs the compiled
flash-attention kernel). Every request must return ``len(prompt) + 16``
tokens. The prefill logits are held to a float32 reference on the same
chip: in float32 at full matmul precision the flash and jnp paths must
agree to ``KERNEL_TOL`` of the largest logit, and the served bf16 flash
logits may be off the reference by at most ``SERVED_FACTOR`` times what the
bf16 jnp path is off. (Held to each other in bf16, the two paths differ
by about 1.5% of the largest logit after 32 layers — as much as each
differs from float32 — so no tight bound between them exists.)

``--chips 4`` runs only what exists across chips: a ``Scheduler`` on a
4-way launch mesh serves ``SHARDED_LAUNCHES`` launches in one dispatch and
must match, bit for bit, the same launches on a single-device
``Scheduler`` on chip 0; a four-config ``Fleet`` on the mesh must bind each
config to its own chip and leave each executor's buffers there.

Output: one JSON object per phase (host-clock compile and wall seconds of
this run, launches served, checks passed — not benchmark numbers), then
the last line ``{"ok": true, "device": {...}}``. Every check raises on
failure, so a failed phase exits non-zero and prints no ``ok`` line. Off
a TPU the script exits non-zero before doing anything: there is no CPU
fallback.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
import zlib
from pathlib import Path

import numpy as np

SRC = Path(__file__).resolve().parent / "src"

SEED = 0               # memory images and model parameters
LAUNCHES = 4           # launches per bench in phase A
SHARDED_LAUNCHES = 16  # launches in the --chips 4 dispatch
SHARDED_BENCH = "xcorr"
# the --chips 4 fleet checks placement, so it leaves out the O(n^2)
# benches phase A already serves
FLEET_BENCHES = ("mat_mul", "copy", "vec_mul", "fir", "div_int", "reduction")
MEMSYSTEMS = ("shared", "banked")
MODEL = "smollm-360m"
N_PROMPTS = 4
PROMPT_LENS = (128, 512)   # inclusive range of the seeded prompt lengths
MAX_NEW = 16
# float32 flash vs jnp prefill logits at full matmul precision: the same
# math in another summation order (5e-7 of max|logit| on a v5e); the
# kernel's dots at default (reduced) precision miss it by 170x
KERNEL_TOL = 1e-4
# served bf16 flash logits may be off float32 by at most twice what the
# bf16 jnp path is off (1.10x on a v5e): a kernel bug in bf16 is larger
SERVED_FACTOR = 2.0

# Per launch (cycles, instrs, mem_ops, hits, misses, steps) for SEED's
# images at Table III sizes, keyed "<n_cus>cu/<memsys>", from single-launch
# ``run_kernel`` on the CPU backend (``launch_pins``). Simulated cycles are
# bit-identical on every backend (DESIGN.md §Invariants).
PINS = {
    "8cu/shared": {
        "mat_mul": 4 * [(44912, 41600, 528384, 241920, 286464, 650)],
        "copy": 4 * [(18432, 2048, 65536, 0, 65536, 32)],
        "vec_mul": 4 * [(55296, 6144, 196608, 0, 196608, 96)],
        "fir": 4 * [(12076, 9664, 134928, 61080, 73848, 151)],
        "div_int": 4 * [(6656, 384, 12288, 0, 12288, 6)],
        "xcorr": 4 * [(2579210, 2492864, 33558528, 29263907, 4294621, 40967)],
        "parallel_sel": [
            (494116, 490068, 4198400, 4196155, 2245, 15496),
            (492644, 489446, 4198400, 4196157, 2243, 15464),
            (492867, 489334, 4198400, 4196171, 2229, 15435),
            (492333, 489101, 4198400, 4196173, 2227, 15455),
        ],
        "reduction": 4 * [(37056, 4160, 66048, 0, 66048, 520)],
    },
    "8cu/banked": {
        "mat_mul": 4 * [(52080, 41600, 528384, 241920, 286464, 650)],
        "copy": 4 * [(18432, 2048, 65536, 0, 65536, 32)],
        "vec_mul": 4 * [(55296, 6144, 196608, 0, 196608, 96)],
        "fir": 4 * [(12116, 9664, 134928, 114777, 20151, 151)],
        "div_int": 4 * [(6656, 384, 12288, 0, 12288, 6)],
        "xcorr": 4 * [(2593116, 2492864, 33558528, 31659852, 1898676, 40967)],
        "parallel_sel": [
            (500808, 490068, 4198400, 4134262, 64138, 15496),
            (499367, 489446, 4198400, 4135518, 62882, 15464),
            (499518, 489334, 4198400, 4135331, 63069, 15435),
            (499008, 489101, 4198400, 4134972, 63428, 15455),
        ],
        "reduction": 4 * [(266432, 4160, 66048, 0, 66048, 520)],
    },
    "1cu/shared": {
        "mat_mul": 4 * [(337616, 41600, 528384, 423168, 105216, 5200)],
        "copy": 4 * [(32768, 2048, 65536, 0, 65536, 256)],
        "vec_mul": 4 * [(98304, 6144, 196608, 0, 196608, 768)],
        "fir": 4 * [(79512, 9664, 134928, 118417, 16511, 1208)],
        "div_int": 4 * [(31744, 384, 12288, 0, 12288, 48)],
        "xcorr": 4 * [(20021492, 2492864, 33558528, 31230442, 2328086, 313400)],
        "parallel_sel": [
            (3921573, 490068, 4198400, 4191558, 6842, 61497),
            (3916596, 489446, 4198400, 4191560, 6840, 61438),
            (3915700, 489334, 4198400, 4191579, 6821, 61425),
            (3913837, 489101, 4198400, 4191558, 6842, 61361),
        ],
        "reduction": 4 * [(66176, 4160, 66048, 0, 66048, 520)],
    },
}


class SmokeError(RuntimeError):
    """A check of the smoke run failed."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeError(what)


def images(bench, n: int) -> list:
    """``n`` memory images of ``bench``'s shape from ``SEED``: every word
    uniform in [-50, 50), the output region included (the kernel
    overwrites it)."""
    rng = np.random.default_rng([SEED, zlib.crc32(bench.name.encode())])
    return [rng.integers(-50, 50, bench.gpu_mem.shape[0]).astype(np.int32)
            for _ in range(n)]


def reference(bench, mem) -> np.ndarray:
    with np.errstate(divide="ignore"):      # div_int: x // 0 == 0, as DIV
        return bench.ref(mem, bench.gpu_n)


def cfg_key(cfg) -> str:
    return f"{cfg.n_cus}cu/{cfg.memsys}"


def launch_stats(info: dict) -> tuple:
    return tuple(int(info[k]) for k in
                 ("cycles", "instrs", "mem_ops", "hits", "misses", "steps"))


def launch_pins(benches: dict, n: int, cfg) -> dict:
    """``launch_stats`` of each bench's ``n`` seeded launches, each run
    alone through ``run_kernel`` — how ``PINS`` was made (on the CPU
    backend) and what the tests compare the served path with."""
    from repro.ggpu.engine import run_kernel
    return {name: [launch_stats(run_kernel(b.gpu_prog, m, b.gpu_items,
                                           cfg)[1])
                   for m in images(b, n)]
            for name, b in benches.items()}


@contextlib.contextmanager
def compile_clock():
    """Backend compiles (persistent-cache reads included), their seconds,
    and the persistent-cache reads of what runs inside, as counted by
    ``repro.tracing``'s compile counter; the dict it yields is filled with
    the totals on exit."""
    from repro import tracing
    tracing.reset_counters()
    tracing.enable()
    totals: dict = {}
    try:
        yield totals
    finally:
        tracing.disable()
        rows = tracing.counters().values()
        totals.update(compile_s=sum(r["compile_s"] for r in rows),
                      compiles=sum(r["compiles"] for r in rows),
                      cache_hits=sum(r["cache_reads"] for r in rows))


def _check_served(results, expect: dict, pins: dict, where) -> None:
    """Every expected tag came back once, with the reference output and
    its pinned cycles/stats. ``expect`` maps tag -> (bench, image, k);
    ``where(result)`` names the pin table of the device that served it."""
    got = sorted(r.info["tag"] for r in results)
    check(got == sorted(expect), f"tickets returned: {len(got)} of "
          f"{len(expect)}")
    for r in results:
        bench, mem, k = expect[r.info["tag"]]
        check(np.array_equal(r.mem, reference(bench, mem)),
              f"{r.info['tag']}: output differs from the reference")
        key = where(r)
        pinned = pins[key][bench.name][k]
        check(launch_stats(r.info) == tuple(pinned),
              f"{r.info['tag']} on {key}: cycles/stats "
              f"{launch_stats(r.info)} != pinned {tuple(pinned)}")


def phase_a(benches: dict, pins: dict, *, launches: int = LAUNCHES,
            memsystems=MEMSYSTEMS) -> dict:
    """The served simulator (module doc). Returns the phase's report."""
    from repro.ggpu.engine import GGPUConfig
    from repro.serve import Fleet, Scheduler

    imgs = {name: images(b, launches) for name, b in benches.items()}
    expect = {f"{name}/{k}": (b, imgs[name][k], k)
              for name, b in benches.items() for k in range(launches)}
    report = {"phase": "A", "benches": list(benches), "launches_served": 0,
              "memsys": {}}
    checks = []
    with compile_clock() as clock:
        t_phase = time.perf_counter()
        for memsys in memsystems:
            cfg = GGPUConfig(n_cus=8, memsys=memsys)
            sched = Scheduler(cfg)
            for name, b in benches.items():
                for k, mem in enumerate(imgs[name]):
                    sched.submit_request(_request(b, mem, name, k))
            t0 = time.perf_counter()
            results = sched.drain()
            wall = time.perf_counter() - t0
            check(not sched.quarantined,
                  f"{memsys}: quarantined {sorted(sched.quarantined)}")
            _check_served(results, expect, pins, lambda r: cfg_key(cfg))
            check(sched.executor.stats.dispatches == len(benches),
                  f"{memsys}: {sched.executor.stats.dispatches} dispatches "
                  f"for {len(benches)} benches (one cohort each)")
            report["memsys"][memsys] = {"wall_s": wall,
                                        "launches": len(results)}
            report["launches_served"] += len(results)
            checks.append(f"scheduler {cfg_key(cfg)}: {len(results)} "
                          "launches exact vs reference and pins, one "
                          "cohort per bench, none quarantined")

        fleet = Fleet([(f"{c}cu", GGPUConfig(n_cus=c)) for c in (8, 1)])
        for k in range(launches):                 # one mixed trace
            for name, b in benches.items():
                fleet.submit_request(_request(b, imgs[name][k], name, k))
        t0 = time.perf_counter()
        results = fleet.drain()
        wall = time.perf_counter() - t0
        check(not fleet.quarantined,
              f"fleet: quarantined {sorted(fleet.quarantined)}")
        cfgs = {d.name: d.cfg for d in fleet.devices}
        _check_served(results, expect, pins,
                      lambda r: cfg_key(cfgs[r.info["device"]]))
        placed = {d.name: sum(1 for r in results
                              if r.info["device"] == d.name)
                  for d in fleet.devices}
        report["fleet"] = {"wall_s": wall, "placement": placed}
        report["launches_served"] += len(results)
        checks.append(f"fleet {placed}: every ticket back, exact vs "
                      "reference and pins, none quarantined")
        report["wall_s"] = time.perf_counter() - t_phase
    report.update(clock, checks=checks)
    return report


def _request(bench, mem, name: str, k: int):
    from repro.serve import Request
    return Request(bench.gpu_prog, mem, bench.gpu_items, tag=f"{name}/{k}",
                   out_region=(bench.gpu_out.start, bench.gpu_out.stop))


def phase_b(cfg, *, prompt_lens=PROMPT_LENS, max_new: int = MAX_NEW
            ) -> dict:
    """One model served at ``cfg``'s widths (module doc). ``cfg`` is taken
    with ``use_pallas=True`` for serving and compared with its jnp twin."""
    import jax
    import jax.numpy as jnp

    from repro.models import model as M
    from repro.models.schema import init_params
    from repro.serve import Engine, EngineConfig

    flash = cfg.replace(use_pallas=True)
    plain = cfg.replace(use_pallas=False)
    rng = np.random.default_rng(SEED)
    lens = rng.integers(prompt_lens[0], prompt_lens[1] + 1, N_PROMPTS)
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist() for n in lens]
    checks = []
    with compile_clock() as clock:
        t_phase = time.perf_counter()
        params = init_params(cfg, jax.random.PRNGKey(SEED))
        engine = Engine(flash, params, EngineConfig(slots=N_PROMPTS))
        t0 = time.perf_counter()
        outs = engine.generate(prompts, max_new=max_new)
        gen_wall = time.perf_counter() - t0
        check(len(outs) == len(prompts), "a request got no answer")
        for p, o in zip(prompts, outs):
            check(len(o) == len(p) + max_new,
                  f"returned {len(o)} tokens for a {len(p)}-token prompt")
            check(o[:len(p)] == p, "the prompt was not echoed back intact")
        checks.append(f"{len(prompts)} requests each returned prompt + "
                      f"{max_new} tokens")

        # prefill logits of the served batch, left-padded as the engine
        # pads it. In bf16 two correct attention paths drift apart over 32
        # layers by as much as each drifts from float32, so the kernel is
        # held to the jnp path in float32 at full matmul precision, and
        # the served bf16 path to the float32 reference
        plen = max(lens)
        batch = np.zeros((len(prompts), plen), np.int32)
        for r, p in enumerate(prompts):
            batch[r, plen - len(p):] = p
        tokens = jnp.asarray(batch)

        def logits(c):
            out = np.asarray(M.prefill(params, c, tokens=tokens)[0],
                             np.float32)
            check(np.isfinite(out).all(), "non-finite prefill logits")
            return out

        f32 = cfg.replace(compute_dtype="float32")
        with jax.default_matmul_precision("highest"):
            ref = logits(f32.replace(use_pallas=False))
            kernel = logits(f32.replace(use_pallas=True))
        served, plain_bf16 = logits(flash), logits(plain)
        scale = float(np.max(np.abs(ref)))
        kernel_diff = float(np.max(np.abs(kernel - ref)))
        served_err = float(np.max(np.abs(served - ref)))
        plain_err = float(np.max(np.abs(plain_bf16 - ref)))
        bf16_diff = float(np.max(np.abs(served - plain_bf16)))
        check(kernel_diff <= KERNEL_TOL * scale,
              f"float32 flash vs jnp prefill logits: max|diff| "
              f"{kernel_diff} > {KERNEL_TOL} * max|logit| {scale}")
        checks.append(f"float32 flash vs jnp prefill logits: max|diff| "
                      f"{kernel_diff} <= {KERNEL_TOL} * max|logit| {scale}")
        check(served_err <= SERVED_FACTOR * plain_err,
              f"served bf16 flash prefill logits off the float32 reference "
              f"by {served_err} > {SERVED_FACTOR} * jnp path's {plain_err}")
        checks.append(f"served bf16 flash logits off float32 by {served_err}"
                      f" <= {SERVED_FACTOR} * jnp path's {plain_err}")
        wall = time.perf_counter() - t_phase
    return {"phase": "B", "model": cfg.name, "layers": cfg.n_layers,
            "d_model": cfg.d_model, "prompt_lens": [int(n) for n in lens],
            "max_new": max_new, "launches_served": len(outs),
            "generate_wall_s": gen_wall, "wall_s": wall,
            "logit_max_abs": scale, "f32_flash_vs_jnp_max_diff": kernel_diff,
            "bf16_flash_vs_f32_max_diff": served_err,
            "bf16_jnp_vs_f32_max_diff": plain_err,
            "bf16_flash_vs_jnp_max_diff": bf16_diff,
            **clock, "checks": checks}


def phase_sharded(bench, devices, fleet_benches: dict, *,
                  n: int = SHARDED_LAUNCHES) -> dict:
    """The path that exists only across chips (module doc): ``devices``
    are the chips of the launch mesh."""
    import jax

    from repro.ggpu.engine import GGPUConfig
    from repro.serve import Fleet, Scheduler

    mesh = jax.sharding.Mesh(np.asarray(devices), ("data",))
    cfg = GGPUConfig(n_cus=8)
    imgs = images(bench, n)
    checks = []
    with compile_clock() as clock:
        t_phase = time.perf_counter()
        served = {}
        sharded = Scheduler(cfg, mesh=mesh)
        check(sharded.executor.shards == len(devices),
              "the launch mesh does not split the launch axis")
        for label, sched in (("sharded", sharded),
                             ("chip0", Scheduler(cfg, device=devices[0]))):
            for k, mem in enumerate(imgs):
                sched.submit_request(_request(bench, mem, bench.name, k))
            t0 = time.perf_counter()
            results = sched.drain()
            served[label] = {"wall_s": time.perf_counter() - t0,
                             "results": results}
            check(not sched.quarantined, f"{label}: launches quarantined")
            check(len(results) == n, f"{label}: {len(results)} of {n} back")
            check(sched.executor.stats.dispatches == 1,
                  f"{label}: {sched.executor.stats.dispatches} dispatches")
        for a, b, mem in zip(served["sharded"]["results"],
                             served["chip0"]["results"], imgs):
            check(np.array_equal(a.mem, b.mem)
                  and np.array_equal(a.mem, reference(bench, mem)),
                  f"{a.info['tag']}: sharded output differs")
            check(launch_stats(a.info) == launch_stats(b.info),
                  f"{a.info['tag']}: sharded cycles/stats "
                  f"{launch_stats(a.info)} != chip 0 {launch_stats(b.info)}")
        checks.append(f"{n} {bench.name} launches in one {len(devices)}-way "
                      "dispatch bit-identical to one chip and the reference")

        fleet_cus = (8, 4, 2, 1)
        fleet = Fleet([(f"{c}cu", GGPUConfig(n_cus=c)) for c in fleet_cus],
                      mesh=mesh)
        bound = [d.device for d in fleet.devices]
        check(len(set(bound)) == len(devices) and set(bound) == set(devices),
              f"fleet devices bound to {bound}, not one chip each")
        expect = {}
        for name, b in fleet_benches.items():
            for k, mem in enumerate(images(b, 2)):
                expect[f"{name}/{k}"] = (b, mem)
                fleet.submit_request(_request(b, mem, name, k))
        for d in fleet.devices:
            d.scheduler.dispatch()
            for chunk in d.scheduler.inflight:
                check(chunk.handle.devices() == {d.device},
                      f"{d.name}: final buffers on {chunk.handle.devices()}"
                      f", not its chip {d.device}")
        t0 = time.perf_counter()
        results = fleet.drain()
        fleet_wall = time.perf_counter() - t0
        check(not fleet.quarantined, "fleet: launches quarantined")
        check(sorted(r.info["tag"] for r in results) == sorted(expect),
              "fleet: not every ticket came back")
        for r in results:
            b, mem = expect[r.info["tag"]]
            check(np.array_equal(r.mem, reference(b, mem)),
                  f"fleet {r.info['tag']}: output differs")
        placed = {d.name: sum(1 for r in results
                              if r.info["device"] == d.name)
                  for d in fleet.devices}
        check(all(placed.values()), f"fleet left a chip idle: {placed}")
        checks.append(f"fleet of {len(fleet_cus)} configs on "
                      f"{len(devices)} distinct chips, buffers on their "
                      f"chip, outputs exact: {placed}")
        wall = time.perf_counter() - t_phase
    return {"phase": "sharded", "bench": bench.name, "launches": n,
            "chips": len(devices),
            "sharded_wall_s": served["sharded"]["wall_s"],
            "chip0_wall_s": served["chip0"]["wall_s"],
            "fleet_wall_s": fleet_wall, "fleet_placement": placed,
            "launches_served": 2 * n + len(results), "wall_s": wall,
            **clock, "checks": checks}


def _emit(report: dict) -> None:
    report["timings"] = "host clock of this run; not benchmark numbers"
    print(json.dumps(report), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded path, on four chips")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {devices[0].platform}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} chips; "
              f"JAX found {len(devices)}", file=sys.stderr)
        return 1
    if str(SRC) not in sys.path:
        sys.path.insert(0, str(SRC))
    from repro.launch.compile_cache import enable_compile_cache
    from repro.ggpu import programs
    cache_dir = enable_compile_cache()
    print(json.dumps({"compile_cache": cache_dir}), flush=True)

    benches = programs.all_benches()
    if args.chips == 4:
        _emit(phase_sharded(benches[SHARDED_BENCH], devices[:4],
                            fleet_benches={n: benches[n]
                                           for n in FLEET_BENCHES}))
    else:
        from repro.configs import get_config
        _emit(phase_a(benches, PINS))
        _emit(phase_b(get_config(MODEL)))
    dev = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": args.chips}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
