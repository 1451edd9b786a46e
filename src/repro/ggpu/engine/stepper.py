"""Stepper: composes the engine stages into the jitted SIMT machine.

The whole machine is one ``jax.lax.while_loop`` over vectorized (W, L)
tensors, jitted once per (program shape, config, opcode set). Each loop
iteration retires up to ``cfg.fuse`` lockstep rounds (**fused dispatch**);
within a fused iteration, a round whose in-flight instructions are all
straight-line (no load/store) takes a fast path that skips the memory
system entirely. Both are wall-clock optimizations only: results, cycles,
and stats are bit-identical to one-round-per-iteration dispatch
(DESIGN.md §Invariants).

The core simulates a **cohort** of ``B`` independent machines by folding
the batch into the wavefront axis (element e owns wavefronts
[e*W, (e+1)*W) and the memory words [e*M, (e+1)*M)); cycles/stats/steps
are tracked per element. One launch is a cohort of one.

**One entry point.** ``launch(progs, mems, n_items, cfg)`` dispatches a
sequence of launches and returns a ``LaunchHandle`` future while the
device still runs. It picks the path from its input:

  * every launch shares program bytes, item count and memory size (the
    serving layer's ``Request.kernel_key``): a **cohort**, one
    ``_run_cohort`` call over the concatenated images — per-round fixed
    costs are amortized and the straight-line fast path stays a real
    branch;
  * otherwise a **batch**: programs padded with HALT words and images
    zero-padded to a common envelope, ``jax.vmap``-ed over the stepper
    (``_run_batch``). Fully general, but vmap turns the fast-path branch
    into a select. Each launch's address clip still binds at its own
    memory size, so the padding is invisible.

``run_kernel`` is the blocking single-launch wrapper; ``legacy=True``
there selects the seed-faithful reference stepper (one round per
iteration, one-hot scatter cache accounting, dense writeback, unpruned
datapath) for differential testing. Per-launch cycles/stats are exact on
every path: HALT and zero padding are state-invisible to the machine, and
cohort elements are fully isolated.

**Sharding.** A ``mesh`` (e.g. ``repro.launch.mesh.make_launch_mesh()``)
whose data-parallel extent is above one splits the launch axis of a
multi-launch call across its devices with ``shard_map``: each device runs
its *own* ``while_loop`` over its slice — no cross-device collective
anywhere — so a device retires its shard as soon as its own launches
halt. Cohorts pad with copies of the first image, batches with 1-item
HALT fillers; padding is sliced away at resolution and never observable.
Cohort sizes are bucketed to powers of two per shard (``cohort_rows``,
sharded or not), so open-loop traffic with arbitrary pending counts
compiles O(log B) steppers rather than one per cohort size. Partition
specs come from the ``repro.sharding.rules`` engine (the ``"launch"``
activation kind). CPU CI simulates 8 devices with
``XLA_FLAGS=--xla_force_host_platform_device_count=8``.

**One memory view.** Every staged and final memory is ``S`` staging rows
of ``b`` launch images of ``msize`` words plus a write sink —
``(S, b*msize + 1)``: a flat cohort is ``S = 1, b = rows`` (its one row
kept 1-D), a sharded cohort ``S = shards``, a batch ``S = launches,
b = 1`` (``msize`` the padded envelope, each launch keeping its own
size). Launch ``i`` lives at ``divmod(i, b)``; the patch applier, the
slicer and every handle method work through this view.

**One patch form.** ``Patch(lo, hi, block, rows=None, op="set"|"xor")``
overwrites (or XORs into) words ``[lo, hi)`` of the launches ``rows``
(``None``: all of them) in the staged buffer, on the device, before the
stepper consumes it — ``block`` row ``k`` feeds launch ``rows[k]``. A call
takes a list, applied in order by one jitted applier on the row view.
With ``LaunchHandle.device_mem``/``device_mem_block`` as producers, this
chains kernels with no host transfer (DESIGN.md §Kernel graphs); the
fault injector's bit flips ride the same path as ``op="xor"``.

**Async.** Three properties make the pipeline cheap (DESIGN.md §Async
launch pipeline):

  * **donation** — the staged memory image (host copy + appended write
    sink) is donated to XLA (``donate_argnums``), so the final memory
    aliases the input buffer instead of allocating a second envelope.
    Caller arrays are never donated: staging always copies host-side.
  * **lazy, sliced download** — resolving a handle fetches only the tiny
    ``done/cycles/stats/step`` arrays; memory is pulled on first access,
    and a declared ``out_region=(lo, hi)`` downloads just that slice of
    each launch's image (``(0, 0)``: cycles-only, no transfer at all).
  * **async dispatch** — the handle returns while the device still runs,
    so the caller can plan, stage, and dispatch the next launch during
    the current one's compute (the serving scheduler's pipelined drain).
"""
from __future__ import annotations

import functools
from typing import Any, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.ggpu import isa
from repro.ggpu.engine import alu, frontend, scheduler
from repro.ggpu.engine.config import GGPUConfig
from repro.ggpu.engine.memsys import SharedCache, get_memsys, load_store


class MachineState(NamedTuple):
    pc: jax.Array          # (B*W, L) int32
    regs: jax.Array        # (B*W, 32, L) int32 (register-major: row reads)
    done: jax.Array        # (B*W, L) bool
    mem: jax.Array         # (B*M+1,) int32 (last slot = write sink)
    tags: jax.Array        # memsys tag state (shape per organization)
    cycles: jax.Array      # (B,) int32 (lockstep-round total per element)
    stats: jax.Array       # (B, 4) int32: instrs, mem_ops, hits, misses
    step: jax.Array        # (B,) int32


def _n_wavefronts(n_items: int, cfg: GGPUConfig) -> int:
    L = cfg.wavefront
    W = (n_items + L - 1) // L
    # the per-CU residency ranking reshapes (W,) -> (W/n_cus, n_cus); round
    # W up with always-done wavefronts when it would be ragged (state of an
    # invalid wavefront never changes, so this is result/cycle-neutral)
    if W > cfg.n_cus * cfg.max_wf_per_cu and W % cfg.n_cus:
        W += cfg.n_cus - W % cfg.n_cus
    return W


def _build_core(cfg: GGPUConfig, B: int, W: int, prog_len: int, msize: int,
                ops, legacy: bool = False):
    """Returns ``core(prog, mem_sink, n_items) -> MachineState`` for one
    static machine shape: ``B`` cohort elements of ``W`` wavefronts each,
    ``mem_sink`` the concatenated (B*msize + 1,) memory images with the
    write sink already appended (callers stage it host-side so the jitted
    wrappers can donate the buffer — the final memory aliases it). ``ops``
    is the static opcode set for decode specialization (None = unpruned);
    ``legacy`` selects the seed-faithful reference round."""
    L = cfg.wavefront
    n_cus = cfg.n_cus
    memsys = get_memsys(cfg.memsys)
    if legacy and not isinstance(memsys, SharedCache):
        raise ValueError("legacy reference stepper only models 'shared'")
    if legacy and cfg.pipeline_depth:
        raise ValueError("legacy reference stepper predates the "
                         "pipeline_depth knob (seed model: depth 0 only)")
    fuse = 1 if legacy else max(1, cfg.fuse)
    ops_present = None if ops is None else frozenset(ops)
    has_mem = ops_present is None or bool({isa.LW, isa.SW} & ops_present)

    elem_of_w = jnp.repeat(jnp.arange(B, dtype=jnp.int32), W)   # (B*W,)
    cu_of_w = jnp.tile(jnp.arange(W, dtype=jnp.int32) % n_cus, B)
    gid = jnp.tile(
        (jnp.arange(W)[:, None] * L + jnp.arange(L)[None, :])
        .astype(jnp.int32), (B, 1))                             # elem-local
    mem_off = (elem_of_w * msize)[:, None]                      # (B*W, 1)
    sink = B * msize
    is_branch = jnp.asarray(isa.IS_BRANCH)
    extra = jnp.asarray(
        isa.SCALAR_EXTRA if cfg.pes_per_cu == 1 else isa.GPU_EXTRA)
    zeros_e = jnp.zeros((B,), jnp.int32)

    def per_elem_sum(x):
        return jnp.sum(x.reshape(B, -1), axis=1).astype(jnp.int32)

    def core(prog, mem_sink, n_items, msize_clip):
        """``msize_clip`` is the launch's own memory size (traced): the
        address clip must bind at each launch's boundary, not the padded
        batch envelope, or an out-of-range access would read the padding
        instead of the launch's last word as a single run does."""
        n_items = n_items.astype(jnp.int32)
        msize_clip = msize_clip.astype(jnp.int32)
        lane_valid = gid < n_items
        st = MachineState(
            pc=jnp.zeros((B * W, L), jnp.int32),
            regs=jnp.zeros((B * W, isa.N_REGS, L), jnp.int32),
            done=~lane_valid,
            mem=mem_sink,
            tags=memsys.init_tags(cfg, B),
            cycles=jnp.zeros((B,), jnp.int32),
            stats=jnp.zeros((B, 4), jnp.int32),
            step=jnp.zeros((B,), jnp.int32),
        )

        def round_step(s: MachineState) -> MachineState:
            # masking `active` by each element's running predicate makes a
            # post-halt (or past-max_steps) round an exact no-op for that
            # element — no per-round control flow needed, which keeps fused
            # sub-rounds branch-free while step/cycle accounting stays
            # identical to one-round-per-iteration dispatch. The stages
            # carry named scopes (schedule, frontend, alu, memsys, cost)
            # in their ops' metadata, so a trace can split the round
            with jax.named_scope("schedule"):
                runvec = (~jnp.all(s.done.reshape(B, -1), axis=1)) \
                    & (s.step < cfg.max_steps)                  # (B,)
                active, _ = scheduler.select_resident(
                    s.done, n_cus=n_cus, max_wf_per_cu=cfg.max_wf_per_cu,
                    n_elems=B, force_rank=legacy)
                active = active & jnp.repeat(runvec, W)[:, None]
            with jax.named_scope("frontend"):
                f = frontend.fetch_decode(prog, prog_len, s.pc, active,
                                          s.regs)
            with jax.named_scope("alu"):
                res = alu.select_alu(f.op, f.a, f.b, f.imm, ops_present)
            with jax.named_scope("frontend"):
                res = frontend.apply_intrinsics(res, f.op, gid, n_items, L,
                                                ops_present)

            @jax.named_scope("memsys")
            def mem_round(res):
                addr_local = jnp.clip(f.a + f.imm, 0, msize_clip - 1)
                is_load = f.op == isa.LW
                is_store = f.op == isa.SW
                mem, loaded, mem_mask = load_store(
                    s.mem, addr_local + mem_off, f.b, f.exec_m, is_load,
                    is_store, sink, always_scatter=legacy)
                res = jnp.where(is_load, loaded, res)
                if legacy:
                    cr = memsys.access(s.tags, addr_local, mem_mask,
                                       cu_of_w=cu_of_w, elem_of_w=elem_of_w,
                                       n_elems=B, cfg=cfg, one_hot=True)
                else:
                    cr = memsys.access(s.tags, addr_local, mem_mask,
                                       cu_of_w=cu_of_w, elem_of_w=elem_of_w,
                                       n_elems=B, cfg=cfg)
                return (res, mem, cr.tags, cr.hit_service, cr.fill_cycles,
                        per_elem_sum(mem_mask), per_elem_sum(cr.hit),
                        per_elem_sum(cr.miss))

            def alu_round(res):
                return (res, s.mem, s.tags, zeros_e, zeros_e, zeros_e,
                        zeros_e, zeros_e)

            if not has_mem:
                out = alu_round(res)
            elif fuse > 1:
                # fused-dispatch fast path: straight-line rounds (no lane
                # touching memory) skip the cache model and the mem scatter
                any_mem = jnp.any((f.op == isa.LW) | (f.op == isa.SW))
                out = jax.lax.cond(any_mem, mem_round, alu_round, res)
            else:                      # legacy dispatch: memsys every round
                out = mem_round(res)
            res, mem, tags, hit_service, fill, n_mem, n_hit, n_miss = out

            with jax.named_scope("frontend"):
                regs = frontend.writeback(s.regs, f, res, is_branch,
                                          dense=legacy)
            with jax.named_scope("alu"):
                taken = alu.branch_taken(f.op, f.a, f.b, ops_present) \
                    & f.exec_m
            with jax.named_scope("frontend"):
                pc, done = frontend.advance(s.pc, s.done, f, taken)
            with jax.named_scope("cost"):
                if cfg.pipeline_depth > 0:
                    # pipeline-latency feedback: each planner-inserted
                    # stage adds one un-bypassed dependency bubble per
                    # issuing wavefront and one refill cycle when the
                    # wavefront takes a branch
                    pipe_stall = cfg.pipeline_depth * (
                        jnp.any(f.exec_m, axis=1).astype(jnp.int32)
                        + jnp.any(taken, axis=1).astype(jnp.int32))
                else:
                    pipe_stall = None
                round_t, wf_exec = scheduler.round_cost(
                    f.op[:, 0], f.exec_m, extra=extra,
                    issue_cycles=cfg.issue_cycles, cu_of_w=cu_of_w,
                    n_cus=n_cus, n_elems=B, hit_service=hit_service,
                    fill_cycles=fill, use_scatter=legacy,
                    pipe_stall=pipe_stall)
                cycles = s.cycles + round_t.astype(jnp.int32)
                stats = s.stats + jnp.stack(
                    [per_elem_sum(wf_exec), n_mem, n_hit, n_miss], axis=1)
            return MachineState(pc, regs, done, mem, tags, cycles, stats,
                                s.step + runvec.astype(jnp.int32))

        def still_running(s: MachineState):
            return jnp.any((~jnp.all(s.done.reshape(B, -1), axis=1))
                           & (s.step < cfg.max_steps))

        if fuse == 1:
            body = round_step
        else:
            # fused dispatch: retire up to `fuse` rounds per while_loop
            # iteration (fori_loop keeps the compiled body single-copy)
            def body(s: MachineState) -> MachineState:
                return jax.lax.fori_loop(
                    0, fuse, lambda _, x: round_step(x), s)

        return jax.lax.while_loop(still_running, body, st)

    return core


# The memory argument of each jitted wrapper arrives with the write sink
# already appended and is DONATED: the machine's final memory aliases the
# staged input buffer (same shape/dtype), so a launch allocates one memory
# envelope, not two. Staging (in ``launch``) always copies host-side, so a
# caller's array is never invalidated.

@functools.partial(jax.jit,
                   static_argnames=("cfg", "B", "W", "prog_len", "ops",
                                    "legacy"),
                   donate_argnums=(1,))
def _run_cohort(prog, mems_sink, n_items, cfg, B, W, prog_len, ops,
                legacy=False):
    msize = (mems_sink.shape[0] - 1) // B
    return _build_core(cfg, B, W, prog_len, msize, ops, legacy)(
        prog, mems_sink, n_items, jnp.asarray(msize, jnp.int32))


@functools.partial(jax.jit, static_argnames=("cfg", "W", "prog_len", "ops"),
                   donate_argnums=(1,))
def _run_batch(progs, mems_sink, n_items, msizes, cfg, W, prog_len, ops):
    core = _build_core(cfg, 1, W, prog_len, mems_sink.shape[1] - 1, ops)
    return jax.vmap(core)(progs, mems_sink, n_items, msizes)


# -- sharded execution over a device mesh -----------------------------------

def launch_shards(mesh) -> int:
    """How many ways the launch axis splits over ``mesh``: the product of
    its data-parallel axis sizes (``None``: 1 — no sharding)."""
    if mesh is None:
        return 1
    rules = _launch_rules(mesh)
    return rules.axes_size(rules.dp_axes)


def cohort_rows(B: int, shards: int = 1) -> int:
    """Padded cohort size for a ``B``-launch cohort over ``shards``
    devices: the per-shard slice is rounded up to a power of two, so the
    staged rows are ``shards * 2^ceil(log2(ceil(B/shards)))``. The bucket
    (not ``B``) is what the compiled stepper is traced for — open-loop
    traffic with arbitrary pending counts compiles O(log B) steppers
    instead of one per distinct cohort size, which is what keeps p99
    launch latency flat under Poisson arrivals. Padding elements are
    copies of the cohort's first image; every resolution path slices them
    away before they can be observed."""
    b_local = -(-B // shards)
    return shards * (1 << max(0, b_local - 1).bit_length())


@functools.lru_cache(maxsize=None)
def _launch_rules(mesh):
    """The sharding rule engine bound to ``mesh`` for launch placement
    (no model axes in play: FSDP/sequence sharding off)."""
    from repro.sharding.rules import make_rules
    return make_rules(mesh, fsdp=False, seq_shard=False)


def _launch_spec(mesh, ndim: int):
    """PartitionSpec sharding a leading launch axis of an ``ndim``-array
    over ``mesh``'s data-parallel axes (via the rule engine's ``launch``
    activation kind — the shard count always divides here because
    ``launch`` pads first)."""
    rules = _launch_rules(mesh)
    shards = rules.axes_size(rules.dp_axes)
    spec = rules.activation_spec("launch", (shards,) + (1,) * (ndim - 1))
    assert spec is not None and spec[0] is not None
    return spec


def _launch_sharding(mesh, ndim: int):
    return jax.sharding.NamedSharding(mesh, _launch_spec(mesh, ndim))


@functools.lru_cache(maxsize=None)
def _sharded_cohort_fn(cfg, B_local, W, prog_len, msize, ops, mesh):
    """Jitted sharded cohort stepper: every mesh shard runs an isolated
    ``B_local``-element cohort over its row of the staged memory
    (``(shards, B_local*msize + 1)`` — one write sink per shard). Each
    shard's ``while_loop`` converges on its own launches only; there are
    no collectives. The memory rows keep their leading device axis
    (out-spec sharded), every other state leaf concatenates per-element
    along axis 0 — exactly the unsharded cohort layout for ``shards *
    B_local`` elements."""
    core = _build_core(cfg, B_local, W, prog_len, msize, ops)
    spec = _launch_spec(mesh, 1)
    row_spec = _launch_spec(mesh, 2)

    def local(prog, mem_rows, n_items):
        st = core(prog, mem_rows[0], n_items, jnp.asarray(msize, jnp.int32))
        return st._replace(mem=st.mem[None])

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(jax.sharding.PartitionSpec(), row_spec,
                  jax.sharding.PartitionSpec()),
        out_specs=MachineState(pc=spec, regs=spec, done=spec, mem=row_spec,
                               tags=spec, cycles=spec, stats=spec,
                               step=spec),
        check_vma=False)              # while_loop has no replication rule
    return jax.jit(fn, donate_argnums=(1,))


@functools.lru_cache(maxsize=None)
def _sharded_batch_fn(cfg, W, prog_len, msize, ops, mesh):
    """Jitted sharded heterogeneous-batch stepper: the vmapped launch
    axis is split across the mesh's data-parallel axes; each shard vmaps
    the single-launch core over its local launches and loops until only
    *they* halt."""
    core = _build_core(cfg, 1, W, prog_len, msize, ops)
    spec = _launch_spec(mesh, 1)
    fn = jax.shard_map(jax.vmap(core), mesh=mesh,
                       in_specs=(spec, spec, spec, spec), out_specs=spec,
                       check_vma=False)
    return jax.jit(fn, donate_argnums=(1,))


class KernelLaunchError(RuntimeError):
    """A launch did not halt within ``cfg.max_steps``. ``index`` is the
    position of the failing launch within the call's own argument list."""

    def __init__(self, message: str, index: int = 0):
        super().__init__(message)
        self.index = index


def _static_ops(prog: np.ndarray):
    return tuple(sorted({int(o) for o in prog[:, 0]}))


def _info(cycles: int, stats, steps: int, cfg: GGPUConfig) -> dict:
    return {
        "cycles": cycles,
        "instrs": int(stats[0]),
        "mem_ops": int(stats[1]),
        "hits": int(stats[2]),
        "misses": int(stats[3]),
        "steps": steps,
        "time_us": float(cycles / cfg.freq_mhz),
        "memsys": cfg.memsys,
    }


Region = Optional[Tuple[int, int]]


class Patch(NamedTuple):
    """Words ``[lo, hi)`` of some launches' staged memory, overwritten
    (``op="set"``) or XORed (``op="xor"``: the single-event-upset form; a
    zero row is a no-op) before the stepper runs. ``block`` is
    ``(len(rows), hi - lo)``, row ``k`` feeding launch ``rows[k]``;
    ``rows=None`` means every launch of the call, in order."""
    lo: int
    hi: int
    block: Any
    rows: Optional[Sequence[int]] = None
    op: str = "set"


def _check_patches(patches: Sequence[Patch], sizes: Sequence[int]):
    """Validate each patch against every target launch's own memory
    size (not a padded envelope)."""
    B = len(sizes)
    for p in patches:
        if p.op not in ("set", "xor"):
            raise ValueError(f"patch op must be 'set' or 'xor', "
                             f"got {p.op!r}")
        rows = range(B) if p.rows is None else [int(r) for r in p.rows]
        if len(set(rows)) != len(rows) \
                or not all(0 <= r < B for r in rows):
            raise ValueError(f"patch rows {p.rows} must be distinct "
                             f"launch indices in [0, {B})")
        if not all(0 <= p.lo <= p.hi <= sizes[r] for r in rows):
            raise ValueError(f"patch [{p.lo}, {p.hi}) outside a target "
                             f"launch's memory image (sizes {list(sizes)})")
        if tuple(np.shape(p.block)) != (len(rows), p.hi - p.lo):
            raise ValueError(f"patch [{p.lo}, {p.hi}) on {len(rows)} "
                             f"launch(es) expects a block of shape "
                             f"{(len(rows), p.hi - p.lo)}, got "
                             f"{tuple(np.shape(p.block))}")


def _elements(mem, msize: int):
    """The launch elements of a staged or final memory (device or host
    array) on its row view (module doc), ``(S*b, msize)`` with the write
    sinks left out. A flat cohort's one row stays 1-D (``S = 1``
    implied): on the TPU, reshaping the whole image into one 2-D row
    would relay all of it out."""
    return mem[..., :-1].reshape(-1, msize)


@functools.partial(jax.jit, static_argnames=("msize", "lo", "hi", "op"),
                   donate_argnums=(0,))
def _apply_patch(staged, block, rows, msize, lo, hi, op):
    """One patch on the row view of a staged buffer, as one compiled
    dispatch that donates the buffer: padding elements are never
    targeted. ``rows`` is a device index array (``None``: the first
    ``len(block)`` launches), so a new row set re-uses the compiled
    applier."""
    body = _elements(staged, msize)
    at = slice(0, block.shape[0]) if rows is None else rows
    if op == "xor":
        block = body[at, lo:hi] ^ block
    body = body.at[at, lo:hi].set(block)
    return staged.at[..., :-1].set(body.reshape(staged[..., :-1].shape))


@functools.partial(jax.jit, static_argnames=("B", "msize", "lo", "hi"))
def _slice_rows(mem, B, msize, lo, hi):
    """All ``B`` launches' ``[lo, hi)`` regions of a final memory as one
    fused ``(B, hi - lo)`` device computation — padding elements beyond
    ``B`` are dropped."""
    return _elements(mem, msize)[:B, lo:hi]


def _stage(host: np.ndarray, patches: Sequence[Patch], msize: int,
           sharding) -> jax.Array:
    """Upload a host-staged image (placed by ``sharding`` when sharded)
    and apply ``patches`` to it on the device."""
    staged = jnp.asarray(host) if sharding is None \
        else jax.device_put(host, sharding)
    for p in patches:
        rows = None if p.rows is None \
            else jnp.asarray(np.asarray(p.rows, np.int32))
        staged = _apply_patch(staged, p.block, rows, msize=msize,
                              lo=p.lo, hi=p.hi, op=p.op)
    if patches and sharding is not None:
        # the applier's output placement is the compiler's choice: restore
        # the launch layout (this reshard is what moves a producer's
        # output to its consumer's shard — still no host hop)
        staged = jax.device_put(staged, sharding)
    return staged


def _check_regions(regions: Optional[Sequence[Region]], B: int,
                   sizes: Sequence[int]) -> Optional[List[Region]]:
    """Validate per-launch output regions against each launch's own memory
    size. ``None`` (no slicing) stays ``None`` so the full-image download
    path is taken."""
    if regions is None:
        return None
    regions = list(regions)
    if len(regions) != B:
        raise ValueError(f"out_regions has {len(regions)} entries for "
                         f"{B} launches")
    for r, size in zip(regions, sizes):
        if r is None:
            continue
        lo, hi = r
        if not (0 <= lo <= hi <= size):
            raise ValueError(f"out_region {r} outside memory image "
                             f"[0, {size})")
    if all(r is None for r in regions):
        return None
    return regions


class LaunchHandle:
    """Future for one in-flight launch call.

    ``wait()`` blocks until the device retires the call, fetching only
    the tiny ``done/cycles/stats/step`` arrays, and raises
    ``KernelLaunchError`` (with the failing position in ``index``) when a
    launch hit ``max_steps``. The final memory stays device-resident until
    asked for: ``mem(i)`` downloads launch ``i``'s image — the declared
    ``out_region`` slice when one was given (``(0, 0)``: no transfer at
    all), the full image otherwise. ``results()`` returns the ``(mem,
    info)`` pairs ``run_kernel`` returns, bit-exact.

    The final memory is read through the one row view of the module doc:
    ``S`` staging rows of ``rows // S`` elements of ``msize`` words, of
    which the first ``B`` are real launches (``sizes`` their own memory
    sizes) and the rest padding that no method exposes.

    ``donated`` is the staged device buffer the dispatch consumed; XLA
    invalidates it at dispatch (the final memory aliases it), and the
    handle never reads it — tests assert ``donated.is_deleted()``.
    ``envelope`` is the static signature the engine jitted for this call
    (path, padded rows, wavefronts, program length, memory size, opcode
    set): a call with an envelope seen before re-uses a compiled stepper.
    """

    def __init__(self, final: MachineState, cfg: GGPUConfig, B: int, S: int,
                 rows: int, msize: int, sizes: Sequence[int],
                 regions: Optional[Sequence[Region]], donated,
                 envelope: tuple):
        self._final = final
        self._cfg = cfg
        self._B = B
        self._rows = rows
        self._b = rows // S
        self._msize = msize
        self._sizes = list(sizes)
        self._regions = _check_regions(regions, B, self._sizes)
        self.donated = donated
        self.envelope = envelope
        self._small = None                     # (cycles, stats, steps)
        self._mem_full = None
        self._mems: dict = {}

    def __len__(self) -> int:
        return self._B

    def devices(self) -> set:
        """The JAX devices holding this dispatch's final machine state —
        where a pinned or sharded executor placed the work."""
        return self._final.mem.devices()

    def ready(self) -> bool:
        """Non-blocking: has the device finished this dispatch?"""
        try:
            return bool(self._final.done.is_ready())
        except AttributeError:                 # non-jax array (never async)
            return True

    def wait(self) -> "LaunchHandle":
        """Block until retired; fetch only the small per-launch arrays.
        Raises ``KernelLaunchError`` naming the first failing launch."""
        if self._small is not None:
            return self
        f, R, B = self._final, self._rows, self._B
        # padding elements (rows > B) are sliced away before inspection,
        # including in the failure path
        done = np.asarray(f.done).reshape(R, -1)[:B]
        cycles = np.asarray(f.cycles).reshape(R)[:B]
        stats = np.asarray(f.stats).reshape(R, 4)[:B]
        steps = np.asarray(f.step).reshape(R)[:B]
        for i in range(B):
            if not done[i].all():
                raise KernelLaunchError(
                    f"kernel {i} hit max_steps without halting", i)
        self._small = (cycles, stats, steps)
        return self

    # -- resolution ----------------------------------------------------------

    def info(self, i: int = 0) -> dict:
        cycles, stats, steps = self.wait()._small
        info = _info(int(cycles[i]), stats[i], int(steps[i]), self._cfg)
        info["batch_size"] = self._B
        return info

    def infos(self) -> List[dict]:
        return [self.info(i) for i in range(self._B)]

    def mem(self, i: int = 0) -> np.ndarray:
        """Launch ``i``'s final memory: the declared region slice when one
        was given, the full image otherwise (downloaded once, cached).

        When every launch declares the same region, all downloads collapse
        into **one** fused device slice per call (``device_mem_block``)
        instead of one dispatch per launch."""
        region = self._regions[i] if self._regions is not None else None
        if region is None:
            return self._full_mem(i)
        if i not in self._mems:
            lo, hi = region
            if hi <= lo:
                self._mems[i] = np.zeros(0, np.int32)
            elif all(r == region for r in self._regions):
                block = np.asarray(self.device_mem_block(lo, hi))
                for j in range(self._B):
                    self._mems[j] = block[j]
            else:
                self._mems[i] = np.asarray(self.device_mem(i, region))
        return self._mems[i]

    def _full_mem(self, i: int) -> np.ndarray:
        if self._mem_full is None:
            self._mem_full = _elements(np.asarray(self._final.mem),
                                       self._msize)
        return self._mem_full[i][:self._sizes[i]]

    # -- device-resident access (no host transfer) ---------------------------

    def device_mem(self, i: int = 0,
                   region: Optional[Tuple[int, int]] = None) -> jax.Array:
        """Launch ``i``'s final-memory ``[lo, hi)`` slice as a
        device-resident array (default: the full image). Never blocks and
        never touches the host — this is the producer side of the
        device-resident chaining protocol: feed the result straight into a
        consumer launch's ``Patch``. The returned array only *reads* the
        final memory (donation is unaffected), and XLA sequences it after
        the producing dispatch, so no explicit wait is needed."""
        lo, hi = (0, self._sizes[i]) if region is None else region
        row, slot = divmod(i, self._b)
        base = slot * self._msize
        # slice first: only the slice, never the whole image, is viewed as
        # the (S, hi - lo) rows its launch's row is taken from
        return jnp.atleast_2d(self._final.mem[..., base + lo:base + hi])[row]

    def device_mem_block(self, lo: int, hi: int) -> jax.Array:
        """All ``B`` launches' ``[lo, hi)`` slices as one device-resident
        ``(B, hi - lo)`` array — one fused device op per call, the fast
        path for feeding a whole producer call into a consumer call's
        ``Patch``. Never blocks, never touches the host."""
        return _slice_rows(self._final.mem, self._B, self._msize, lo, hi)

    def results(self) -> List[Tuple[np.ndarray, dict]]:
        """All launches as (mem, info) pairs, in call order."""
        return [(self.mem(i), self.info(i)) for i in range(self._B)]

    def result(self) -> Tuple[np.ndarray, dict]:
        """Single-launch convenience: the (mem, info) pair."""
        if self._B != 1:
            raise ValueError(f"handle holds {self._B} launches; "
                             "use results()")
        return self.mem(0), self.info(0)


def launch(progs: Sequence[np.ndarray], mems: Sequence[np.ndarray],
           n_items: Sequence[int], cfg: GGPUConfig, *,
           out_regions: Optional[Sequence[Region]] = None,
           patches: Sequence[Patch] = (), mesh=None,
           legacy: bool = False) -> LaunchHandle:
    """Dispatch launches ``i`` = (``progs[i]``, ``mems[i]``,
    ``n_items[i]``) as one stepper call, asynchronously: a cohort when they
    all share one kernel, a vmapped batch otherwise, sharded over ``mesh``
    when it has more than one data-parallel shard and there is more than
    one launch (module doc). ``out_regions`` optionally declares one
    download slice per launch (``None`` entries download that launch's
    full image); ``patches`` are applied in order to the staged memory
    before dispatch. ``legacy=True`` runs the seed-faithful reference
    stepper, one launch only."""
    progs = [np.asarray(p, np.int32) for p in progs]
    mems = [np.asarray(m, np.int32) for m in mems]
    n_items = [int(n) for n in n_items]
    B = len(progs)
    if not B or len(mems) != B or len(n_items) != B:
        raise ValueError(f"launch needs one mem and one n_items per "
                         f"program, at least one launch: got {B} progs, "
                         f"{len(mems)} mems, {len(n_items)} n_items")
    if legacy and B > 1:
        raise ValueError("the legacy reference stepper runs one launch "
                         "per call")
    sizes = [m.shape[0] for m in mems]
    patches = list(patches)
    _check_patches(patches, sizes)
    shards = launch_shards(mesh) if B > 1 else 1
    sharding = _launch_sharding(mesh, 2) if shards > 1 else None
    if (len(set(n_items)) == 1 and len(set(sizes)) == 1
            and all(np.array_equal(p, progs[0]) for p in progs[1:])):
        prog, n, msize = progs[0], n_items[0], sizes[0]
        rows = cohort_rows(B, shards)
        b = rows // shards
        padded = mems + [mems[0]] * (rows - B)
        host = [np.concatenate(padded[s * b:(s + 1) * b]
                               + [np.zeros(1, np.int32)])
                for s in range(shards)]
        staged = _stage(host[0] if shards == 1 else np.stack(host),
                        patches, msize, sharding)
        W, P = _n_wavefronts(n, cfg), prog.shape[0]
        ops = None if legacy else _static_ops(prog)
        n_arr = jnp.asarray(n, jnp.int32)
        if shards > 1:
            final = _sharded_cohort_fn(cfg, b, W, P, msize, ops, mesh)(
                jnp.asarray(prog), staged, n_arr)
        else:
            final = _run_cohort(jnp.asarray(prog), staged, n_arr, cfg, rows,
                                W, P, ops, legacy)
        envelope = ("cohort", rows, W, P, msize, ops, legacy)
        S = shards
    else:
        pad = -B % shards
        if pad:                                # 1-item HALT fillers
            progs = progs + [np.zeros((1, progs[0].shape[1]), np.int32)] * pad
            mems = mems + [np.zeros(1, np.int32)] * pad
            n_items = n_items + [1] * pad
        P = max(p.shape[0] for p in progs)
        msize = max(m.shape[0] for m in mems)
        prog_b = np.stack([np.pad(p, ((0, P - p.shape[0]), (0, 0)))
                           for p in progs])              # HALT == all-zeros
        # each row zero-padded to the envelope plus its own write-sink slot
        mem_b = np.stack([np.pad(m, (0, msize + 1 - m.shape[0]))
                          for m in mems])
        rows = S = B + pad
        staged = _stage(mem_b, patches, msize, sharding)
        W = max(_n_wavefronts(k, cfg) for k in n_items)
        ops = tuple(sorted(set().union(*(_static_ops(p) for p in progs))))
        n_arr = jnp.asarray(np.asarray(n_items, np.int32))
        msz_arr = jnp.asarray(np.array([m.shape[0] for m in mems], np.int32))
        if shards > 1:
            final = _sharded_batch_fn(cfg, W, P, msize, ops, mesh)(
                jnp.asarray(prog_b), staged, n_arr, msz_arr)
        else:
            final = _run_batch(jnp.asarray(prog_b), staged, n_arr, msz_arr,
                               cfg, W, P, ops)
        envelope = ("batch", rows, W, P, msize, ops)
    return LaunchHandle(final, cfg, B, S, rows, msize, sizes, out_regions,
                        staged, envelope)


def run_kernel(prog: np.ndarray, mem0: np.ndarray, n_items: int,
               cfg: GGPUConfig, *, legacy: bool = False):
    """Execute one kernel launch; returns (mem_final, info dict).

    ``legacy=True`` runs the seed-faithful reference stepper (identical
    results and cycles, pre-refactor wall-clock) for differential testing
    and as the baseline of ``benchmarks.engine_bench``."""
    return launch([prog], [mem0], [n_items], cfg, legacy=legacy).result()
