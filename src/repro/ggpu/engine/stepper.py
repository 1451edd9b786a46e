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
are tracked per element. ``B == 1`` is the single-launch case.

Entry points:

  * ``run_kernel``        — single launch; exact signature and bit-exact
    results of the original monolithic ``machine.run_kernel``.
    ``legacy=True`` selects the seed-faithful reference stepper
    (one round per iteration, one-hot scatter cache accounting, dense
    writeback, unpruned datapath) for differential testing/benchmarks.
  * ``run_kernel_cohort`` — N launches of the *same kernel* (program,
    n_items, memory shape) over different memory images, folded into one
    stepper call: per-round fixed costs are amortized across the cohort
    and the straight-line fast path stays a real branch. This is the fast
    multi-launch path ``serve.engine.LaunchQueue`` uses.
  * ``run_kernel_batch``  — N heterogeneous launches, padded to a common
    (program, mem) envelope and ``jax.vmap``-ed over the stepper. Fully
    general (different programs), but vmap turns the fast-path branch into
    a select, so prefer cohorts where shapes allow.

Per-launch cycles/stats are exact in all three: padding a program with
HALT words and a memory image with zeros is state-invisible to the
machine, and cohort elements are fully isolated.

**Sharded execution.** The cohort and batch async entry points accept a
``mesh=`` (a ``jax.sharding.Mesh``, e.g. ``repro.launch.mesh.
make_launch_mesh()``): the leading launch axis is then sharded across
the mesh's data-parallel axes with ``shard_map``, so a fleet of N
simulated G-GPU instances maps onto M physical devices. Each device
runs its *own* ``while_loop`` over its slice of the launches — there is
no cross-device collective anywhere in the machine, so a device retires
its shard as soon as its own launches halt. Launch counts that do not
divide the shard count are padded (cohorts with a copy of the first
image, batches with a 1-item HALT filler); padding is sliced away at
resolution and never observable. Cohort sizes are additionally bucketed
to powers of two per shard (``cohort_rows``, sharded or not), so
open-loop serving traffic with arbitrary pending counts compiles
O(log B) steppers rather than one per distinct cohort size — the
compiled-envelope discipline that keeps tail latency flat under Poisson
arrivals. Per-launch results, cycles, and stats
are bit-exact vs the single-device path by construction: cohort
elements are fully isolated, so a B-element cohort split into M local
(B/M)-element cohorts computes identical bits. A mesh whose
data-parallel extent is 1 (or ``mesh=None``) falls back to the
single-device path. Partition specs come from the
``repro.sharding.rules`` rule engine (the ``"launch"`` activation
kind). CPU CI simulates 8 devices with
``XLA_FLAGS=--xla_force_host_platform_device_count=8``.

**Async launch pipeline.** Every entry point has an ``_async`` twin
(``run_kernel_async`` / ``run_kernel_cohort_async`` /
``run_kernel_batch_async``) that returns a ``LaunchHandle`` future
immediately after dispatch instead of blocking on the device. The sync
entry points are thin blocking wrappers over the same jitted callables
(``handle.results()`` right after dispatch), so both paths share one
compile cache and are bit-exact by construction. Three properties make
the async path cheap (DESIGN.md §Async launch pipeline):

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
from typing import List, NamedTuple, Optional, Sequence, Tuple

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
# envelope, not two. Staging (in the *_async entry points) always copies
# host-side, so a caller's array is never invalidated.

@functools.partial(jax.jit,
                   static_argnames=("cfg", "W", "prog_len", "ops", "legacy"),
                   donate_argnums=(1,))
def _run_single(prog, mem_sink, n_items, cfg, W, prog_len, ops,
                legacy=False):
    msize = mem_sink.shape[0] - 1
    return _build_core(cfg, 1, W, prog_len, msize, ops, legacy)(
        prog, mem_sink, n_items, jnp.asarray(msize, jnp.int32))


@functools.partial(jax.jit,
                   static_argnames=("cfg", "B", "W", "prog_len", "ops"),
                   donate_argnums=(1,))
def _run_cohort(prog, mems_sink, n_items, cfg, B, W, prog_len, ops):
    msize = (mems_sink.shape[0] - 1) // B
    return _build_core(cfg, B, W, prog_len, msize, ops)(
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
    activation kind — the shard count always divides here because entry
    points pad first)."""
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


# -- device-resident chaining (patches) --------------------------------------
#
# A *patch* overwrites a region of a launch's staged memory with a device
# array — typically another launch's ``device_mem``/``device_mem_block``
# output — so a consumer kernel reads its producer's result without any
# host transfer. Patches are applied to the freshly staged buffer BEFORE
# the jitted stepper consumes (and donates) it, so they change neither the
# compiled envelope nor the donation discipline. Two forms:
#
#   * per-launch: a sequence with one entry per launch, each ``None`` or a
#     list of ``(dst_lo, dst_hi, src_array)`` tuples (an optional fourth
#     element ``"xor"`` flips bits instead of overwriting — the SEU
#     injection form, see ``repro.faults``);
#   * ``BlockPatch(lo, hi, block)``: one uniform region for every real
#     launch of the chunk, ``block`` row ``j`` feeding launch ``j`` — a
#     single fused device op, the chunk-to-chunk fast path;
#   * ``XorBlockPatch(lo, hi, block)``: same shape contract, but XORed
#     into the staged words rather than overwriting them. A zero row is a
#     no-op, so a chunk-wide SEU plan stays one fused dispatch even when
#     only a few launches are hit (bit-exact off-by-default: injection
#     disabled means the patch is simply absent, not an identity op).


class BlockPatch(NamedTuple):
    """One uniform staged-memory patch across all ``B`` real launches of a
    chunk: ``block`` is ``(B, hi - lo)``; row ``j`` overwrites launch
    ``j``'s words ``[lo, hi)``."""
    lo: int
    hi: int
    block: jax.Array


class XorBlockPatch(NamedTuple):
    """Like :class:`BlockPatch` but ``block`` row ``j`` is XORed into
    launch ``j``'s words ``[lo, hi)`` — the fused single-event-upset
    (bit-flip) injection primitive. Rows of zeros leave their launch
    untouched."""
    lo: int
    hi: int
    block: jax.Array


def _check_patches(patches, B: int, sizes: Sequence[int]):
    """Validate patch bounds against each launch's own memory size."""
    if isinstance(patches, (BlockPatch, XorBlockPatch)):
        lo, hi, block = patches
        if not all(0 <= lo <= hi <= s for s in sizes[:B]):
            raise ValueError(f"block patch [{lo}, {hi}) outside a launch's "
                             f"memory image (sizes {list(sizes[:B])})")
        if tuple(block.shape) != (B, hi - lo):
            raise ValueError(f"block patch expects shape {(B, hi - lo)}, "
                             f"got {tuple(block.shape)}")
        return
    patches = list(patches)
    if len(patches) != B:
        raise ValueError(f"patches has {len(patches)} entries for "
                         f"{B} launches")
    for plist, size in zip(patches, sizes):
        for entry in (plist or ()):
            lo, hi, src = entry[0], entry[1], entry[2]
            if len(entry) > 3 and entry[3] not in ("set", "xor"):
                raise ValueError(f"patch op must be 'set' or 'xor', "
                                 f"got {entry[3]!r}")
            if not (0 <= lo <= hi <= size):
                raise ValueError(f"patch [{lo}, {hi}) outside memory "
                                 f"image [0, {size})")
            if np.shape(src) != (hi - lo,):
                raise ValueError(f"patch [{lo}, {hi}) expects "
                                 f"{hi - lo} words, got {np.shape(src)}")


@functools.partial(jax.jit, static_argnames=("lo", "hi"),
                   donate_argnums=(0,))
def _patch_rows_block(body, block, lo, hi):
    """Jitted ``BlockPatch`` application to a row-per-launch staging
    buffer: one compiled dispatch (donating the staging buffer) instead
    of a handful of eager ops — the patch cost is fixed per chunk, so it
    must not scale the pipelined path's dispatch overhead."""
    return body.at[:block.shape[0], lo:hi].set(block)


@functools.partial(jax.jit, static_argnames=("msize", "lo", "hi"),
                   donate_argnums=(0,))
def _patch_flat_block(staged, block, msize, lo, hi):
    """Jitted ``BlockPatch`` application to a flat cohort/single staging
    buffer (reshape + patch + reflatten fused into one dispatch)."""
    rows = (staged.shape[0] - 1) // msize
    body = staged[:rows * msize].reshape(rows, msize)
    body = body.at[:block.shape[0], lo:hi].set(block)
    return jnp.concatenate([body.reshape(-1), staged[rows * msize:]])


@functools.partial(jax.jit, static_argnames=("lo", "hi"),
                   donate_argnums=(0,))
def _xor_rows_block(body, block, lo, hi):
    """Jitted ``XorBlockPatch`` application to a row-per-launch staging
    buffer: bit-flips land as one compiled dispatch, same cost profile as
    the dependency-feed ``BlockPatch`` fast path."""
    region = body[:block.shape[0], lo:hi]
    return body.at[:block.shape[0], lo:hi].set(region ^ block)


@functools.partial(jax.jit, static_argnames=("msize", "lo", "hi"),
                   donate_argnums=(0,))
def _xor_flat_block(staged, block, msize, lo, hi):
    """Jitted ``XorBlockPatch`` application to a flat cohort/single
    staging buffer."""
    rows = (staged.shape[0] - 1) // msize
    body = staged[:rows * msize].reshape(rows, msize)
    region = body[:block.shape[0], lo:hi]
    body = body.at[:block.shape[0], lo:hi].set(region ^ block)
    return jnp.concatenate([body.reshape(-1), staged[rows * msize:]])


def _patch_rows(body: jax.Array, patches) -> jax.Array:
    """Apply patches to a row-per-launch view of the staged memory."""
    if isinstance(patches, XorBlockPatch):
        lo, hi, block = patches
        return _xor_rows_block(body, block, lo=lo, hi=hi)
    if isinstance(patches, BlockPatch):
        lo, hi, block = patches
        return _patch_rows_block(body, block, lo=lo, hi=hi)
    for i, plist in enumerate(patches):
        for entry in (plist or ()):
            lo, hi, src = entry[0], entry[1], entry[2]
            if len(entry) > 3 and entry[3] == "xor":
                body = body.at[i, lo:hi].set(body[i, lo:hi] ^ src)
            else:
                body = body.at[i, lo:hi].set(src)
    return body


def _patch_flat(staged: jax.Array, msize: int, patches) -> jax.Array:
    """Patch a flat ``(rows*msize + 1,)`` cohort/single staging buffer.
    Padding rows (copies of the first image) stay unpatched — they are
    sliced away at resolution and each launch is isolated, so they are
    never observable."""
    if isinstance(patches, XorBlockPatch):
        lo, hi, block = patches
        return _xor_flat_block(staged, block, msize=msize, lo=lo, hi=hi)
    if isinstance(patches, BlockPatch):
        lo, hi, block = patches
        return _patch_flat_block(staged, block, msize=msize, lo=lo, hi=hi)
    rows = (staged.shape[0] - 1) // msize
    body = staged[:rows * msize].reshape(rows, msize)
    body = _patch_rows(body, patches)
    return jnp.concatenate([body.reshape(-1), staged[rows * msize:]])


@functools.partial(jax.jit, static_argnames=("B", "msize", "lo", "hi"))
def _slice_block(mem, B, msize, lo, hi):
    """All launches' [lo, hi) regions of a flat cohort/single memory as one
    fused (B, hi-lo) device computation — one dispatch per chunk."""
    return mem[:B * msize].reshape(B, msize)[:, lo:hi]


@functools.partial(jax.jit, static_argnames=("lo", "hi"))
def _slice_batch(mem, lo, hi):
    """All launches' [lo, hi) regions of a batched (N, M+1) memory."""
    return mem[:, lo:hi]


@functools.partial(jax.jit, static_argnames=("B", "msize", "lo", "hi"))
def _slice_rows(mem_rows, B, msize, lo, hi):
    """All launches' [lo, hi) regions of a sharded-cohort memory
    (``(shards, B_local*msize + 1)`` device rows) as one fused device
    computation — padding rows beyond ``B`` are dropped."""
    shards = mem_rows.shape[0]
    b_local = (mem_rows.shape[1] - 1) // msize
    flat = mem_rows[:, :b_local * msize].reshape(shards * b_local, msize)
    return flat[:B, lo:hi]


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
    """Future for one in-flight (possibly folded) kernel launch.

    ``wait()`` blocks until the device retires the launch, fetching only
    the tiny ``done/cycles/stats/step`` arrays, and raises
    ``KernelLaunchError`` (with the failing position in ``index``) when a
    launch hit ``max_steps``. The final memory stays device-resident until
    asked for: ``mem(i)`` downloads launch ``i``'s image — the declared
    ``out_region`` slice when one was given (``(0, 0)``: no transfer at
    all), the full image otherwise. ``results()`` returns the same
    ``(mem, info)`` pairs as the sync entry point, bit-exact.

    ``donated`` is the staged device buffer the dispatch consumed; XLA
    invalidates it at dispatch (the final memory aliases it), and the
    handle never reads it — tests assert ``donated.is_deleted()``.

    Sharded dispatches (``mesh=``) pad the launch axis up to the shard
    count: ``rows`` is the padded element count, ``B`` stays the real
    one, and every resolution path slices the padding away before it can
    be observed. Kind ``"shard-cohort"`` additionally remembers that the
    final memory is laid out as per-shard rows rather than one flat
    image.
    """

    def __init__(self, final: MachineState, cfg: GGPUConfig, kind: str,
                 B: int, msize: int, n_keep: Optional[Sequence[int]],
                 regions: Optional[Sequence[Region]], batch_size:
                 Optional[int], donated, rows: Optional[int] = None):
        self._final = final
        self._cfg = cfg
        self._kind = kind
        self._B = B
        self._rows = B if rows is None else rows
        self._msize = msize
        self._n_keep = list(n_keep) if n_keep is not None else None
        self._regions = _check_regions(
            regions, B, self._n_keep if self._n_keep is not None
            else [msize] * B)
        self._batch_size = batch_size
        self.donated = donated
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
        f = self._final
        # padding elements (rows > B) are sliced away before inspection:
        # a sharded dispatch's fillers are never observable, including in
        # the failure path
        done = np.asarray(f.done).reshape(self._rows, -1)[:self._B]
        if self._kind == "batch":
            cycles = np.asarray(f.cycles)[:self._B, 0]
            stats = np.asarray(f.stats)[:self._B, 0]
            steps = np.asarray(f.step)[:self._B, 0]
        else:
            cycles = np.asarray(f.cycles)[:self._B]
            stats = np.asarray(f.stats)[:self._B]
            steps = np.asarray(f.step)[:self._B]
        for i in range(self._B):
            if not done[i].all():
                what = {"single": "kernel", "cohort": f"cohort kernel {i}",
                        "shard-cohort": f"cohort kernel {i}",
                        "batch": f"batched kernel {i}"}[self._kind]
                raise KernelLaunchError(
                    f"{what} hit max_steps without halting", i)
        self._small = (cycles, stats, steps)
        return self

    # -- resolution ----------------------------------------------------------

    def info(self, i: int = 0) -> dict:
        cycles, stats, steps = self.wait()._small
        info = _info(int(cycles[i]), stats[i], int(steps[i]), self._cfg)
        if self._batch_size is not None:
            info["batch_size"] = self._batch_size
        return info

    def infos(self) -> List[dict]:
        return [self.info(i) for i in range(self._B)]

    def mem(self, i: int = 0) -> np.ndarray:
        """Launch ``i``'s final memory: the declared region slice when one
        was given, the full image otherwise (downloaded once, cached).

        Same-kernel chunks declare the same region for every launch, so
        the uniform case collapses all downloads into **one** fused device
        slice per chunk (``_slice_block``) instead of one dispatch per
        launch."""
        region = self._regions[i] if self._regions is not None else None
        if region is None:
            return self._full_mem(i)
        if i not in self._mems:
            lo, hi = region
            if hi <= lo:
                self._mems[i] = np.zeros(0, np.int32)
            elif all(r == region for r in self._regions):
                if self._kind == "batch":
                    block = np.asarray(_slice_batch(self._final.mem, lo, hi))
                elif self._kind == "shard-cohort":
                    block = np.asarray(_slice_rows(
                        self._final.mem, self._B, self._msize, lo, hi))
                else:
                    block = np.asarray(_slice_block(
                        self._final.mem, self._B, self._msize, lo, hi))
                for j in range(self._B):
                    self._mems[j] = block[j]
            elif self._kind == "batch":
                self._mems[i] = np.asarray(self._final.mem[i, lo:hi])
            elif self._kind == "shard-cohort":
                b_local = (self._final.mem.shape[1] - 1) // self._msize
                shard, slot = divmod(i, b_local)
                base = slot * self._msize
                self._mems[i] = np.asarray(
                    self._final.mem[shard, base + lo:base + hi])
            else:
                base = i * self._msize
                self._mems[i] = np.asarray(
                    self._final.mem[base + lo:base + hi])
        return self._mems[i]

    def _full_mem(self, i: int) -> np.ndarray:
        if self._mem_full is None:
            m = np.asarray(self._final.mem)
            if self._kind == "batch":
                self._mem_full = m[:, :-1]
            elif self._kind == "shard-cohort":
                # per-shard rows: drop each row's write sink, flatten the
                # shard axis back into one element-major image stack
                self._mem_full = m[:, :-1].reshape(-1, self._msize)
            else:
                self._mem_full = m[:-1].reshape(self._rows, self._msize)
        row = self._mem_full[i]
        return row[:self._n_keep[i]] if self._n_keep is not None else row

    # -- device-resident access (no host transfer) ---------------------------

    def device_mem(self, i: int = 0,
                   region: Optional[Tuple[int, int]] = None) -> jax.Array:
        """Launch ``i``'s final-memory ``[lo, hi)`` slice as a
        device-resident array (default: the full image). Never blocks and
        never touches the host — this is the producer side of the
        device-resident chaining protocol: feed the result straight into a
        consumer launch's ``patches``. The returned array only *reads*
        the final memory (donation is unaffected), and XLA sequences it
        after the producing dispatch, so no explicit wait is needed."""
        if region is None:
            size = (self._n_keep[i] if self._n_keep is not None
                    else self._msize)
            region = (0, size)
        lo, hi = region
        if self._kind == "batch":
            return self._final.mem[i, lo:hi]
        if self._kind == "shard-cohort":
            b_local = (self._final.mem.shape[1] - 1) // self._msize
            shard, slot = divmod(i, b_local)
            base = slot * self._msize
            return self._final.mem[shard, base + lo:base + hi]
        base = i * self._msize
        return self._final.mem[base + lo:base + hi]

    def device_mem_block(self, lo: int, hi: int) -> jax.Array:
        """All ``B`` launches' ``[lo, hi)`` slices as one device-resident
        ``(B, hi - lo)`` array — one fused device op per chunk, the fast
        path for feeding a whole producer chunk into a consumer chunk's
        ``BlockPatch``. Never blocks, never touches the host."""
        if self._kind == "batch":
            return _slice_batch(self._final.mem, lo, hi)[:self._B]
        if self._kind == "shard-cohort":
            return _slice_rows(self._final.mem, self._B, self._msize,
                               lo, hi)
        return _slice_block(self._final.mem, self._B, self._msize, lo, hi)

    def results(self) -> List[Tuple[np.ndarray, dict]]:
        """All launches as (mem, info) pairs — exactly what the sync entry
        point returns."""
        return [(self.mem(i), self.info(i)) for i in range(self._B)]

    def result(self) -> Tuple[np.ndarray, dict]:
        """Single-launch convenience: the (mem, info) pair."""
        if self._B != 1:
            raise ValueError(f"handle holds {self._B} launches; "
                             "use results()")
        return self.mem(0), self.info(0)


def _stage(mems: Sequence[np.ndarray]) -> jax.Array:
    """Host-copy the image(s) plus the write-sink slot into one fresh
    device buffer — the buffer the jitted wrapper donates."""
    return jnp.asarray(np.concatenate(list(mems)
                                      + [np.zeros(1, np.int32)]))


def run_kernel_async(prog: np.ndarray, mem0: np.ndarray, n_items: int,
                     cfg: GGPUConfig, *, out_region: Region = None,
                     patches=None, legacy: bool = False) -> LaunchHandle:
    """Dispatch a single launch asynchronously; returns a ``LaunchHandle``
    while the device still runs. ``out_region=(lo, hi)`` limits the
    eventual memory download to that slice of the final image. ``patches``
    optionally overwrites regions of the staged memory with device arrays
    before dispatch (a flat list of ``(lo, hi, src)`` — the single-launch
    form of the chunk-level patch protocol above)."""
    prog = np.asarray(prog, np.int32)
    mem0 = np.asarray(mem0, np.int32)
    staged = _stage([mem0])
    if patches is not None:
        msize = mem0.shape[0]
        per_launch = (patches
                      if isinstance(patches, (BlockPatch, XorBlockPatch))
                      else [list(patches)])
        _check_patches(per_launch, 1, [msize])
        staged = _patch_flat(staged, msize, per_launch)
    final = _run_single(
        jnp.asarray(prog), staged,
        jnp.asarray(int(n_items), jnp.int32), cfg,
        _n_wavefronts(int(n_items), cfg), int(prog.shape[0]),
        None if legacy else _static_ops(prog), legacy)
    return LaunchHandle(final, cfg, "single", 1, mem0.shape[0], None,
                        [out_region] if out_region is not None else None,
                        None, staged)


def run_kernel(prog: np.ndarray, mem0: np.ndarray, n_items: int,
               cfg: GGPUConfig, *, legacy: bool = False):
    """Execute a kernel. Returns (mem_final, info dict).

    ``legacy=True`` runs the seed-faithful reference stepper (identical
    results and cycles, pre-refactor wall-clock) for differential testing
    and as the baseline of ``benchmarks.engine_bench``."""
    return run_kernel_async(prog, mem0, n_items, cfg,
                            legacy=legacy).result()


def run_kernel_cohort_async(prog: np.ndarray, mems: Sequence[np.ndarray],
                            n_items: int, cfg: GGPUConfig, *,
                            out_regions: Optional[Sequence[Region]] = None,
                            patches=None, mesh=None) -> LaunchHandle:
    """Dispatch B same-kernel launches as one folded stepper call,
    asynchronously. ``out_regions`` optionally declares one download slice
    per launch (``None`` entries download that launch's full image).
    ``patches`` optionally overwrites regions of the staged memory with
    device arrays before dispatch — a ``BlockPatch`` or one
    ``[(lo, hi, src), ...]`` list per launch (see the patch protocol
    above). ``mesh`` shards the launch axis across the mesh's
    data-parallel devices (see module doc); a 1-extent mesh falls back to
    the single-device path."""
    prog = np.asarray(prog, np.int32)
    mems = [np.asarray(m, np.int32) for m in mems]
    if not mems:
        raise ValueError("empty cohort")
    msize = mems[0].shape[0]
    if any(m.shape[0] != msize for m in mems):
        raise ValueError("cohort memory images must share one shape")
    B = len(mems)
    if patches is not None:
        _check_patches(patches, B, [msize] * B)
    shards = launch_shards(mesh)
    if shards > 1 and B > 1:
        return _dispatch_cohort_sharded(prog, mems, n_items, cfg, mesh,
                                        shards, out_regions, patches)
    rows = cohort_rows(B)
    staged = _stage(mems + [mems[0]] * (rows - B))
    if patches is not None:
        staged = _patch_flat(staged, msize, patches)
    final = _run_cohort(
        jnp.asarray(prog), staged,
        jnp.asarray(int(n_items), jnp.int32), cfg, rows,
        _n_wavefronts(int(n_items), cfg), int(prog.shape[0]),
        _static_ops(prog))
    return LaunchHandle(final, cfg, "cohort", B, msize, None, out_regions,
                        B, staged, rows=rows)


def _dispatch_cohort_sharded(prog, mems, n_items, cfg, mesh, shards,
                             out_regions, patches=None) -> LaunchHandle:
    """Shard a cohort's launch axis over ``mesh``: pad B up to the
    ``cohort_rows`` bucket with copies of the first image (same kernel,
    same halt behavior — sliced away at resolution), stage one memory row
    per shard (its slice of the images plus a private write sink), and
    dispatch the shard_map'd stepper once."""
    B, msize = len(mems), mems[0].shape[0]
    n_rows = cohort_rows(B, shards)
    padded = mems + [mems[0]] * (n_rows - B)
    b_local = n_rows // shards
    rows = np.stack([
        np.concatenate(padded[s * b_local:(s + 1) * b_local]
                       + [np.zeros(1, np.int32)])
        for s in range(shards)])
    staged = jax.device_put(rows, _launch_sharding(mesh, 2))
    if patches is not None:
        # patch the element-major row view, then restore the per-shard
        # row layout + sharding (the resulting reshard is what moves a
        # producer's output to its consumer's shard — still no host hop)
        body = staged[:, :b_local * msize].reshape(n_rows, msize)
        body = _patch_rows(body, patches)
        staged = jax.device_put(
            jnp.concatenate([body.reshape(shards, b_local * msize),
                             staged[:, b_local * msize:]], axis=1),
            _launch_sharding(mesh, 2))
    final = _sharded_cohort_fn(
        cfg, b_local, _n_wavefronts(int(n_items), cfg),
        int(prog.shape[0]), msize, _static_ops(prog), mesh)(
        jnp.asarray(prog), staged, jnp.asarray(int(n_items), jnp.int32))
    return LaunchHandle(final, cfg, "shard-cohort", B, msize, None,
                        out_regions, B, staged, rows=n_rows)


def run_kernel_cohort(prog: np.ndarray, mems: Sequence[np.ndarray],
                      n_items: int, cfg: GGPUConfig
                      ) -> List[Tuple[np.ndarray, dict]]:
    """Execute the same kernel over B memory images as one folded stepper
    call (B*W wavefronts, per-element accounting). Bit-exact per launch."""
    mems = list(mems)                # materialize once: iterators welcome
    if not mems:
        return []
    return run_kernel_cohort_async(prog, mems, n_items, cfg).results()


def run_kernel_batch_async(progs: Sequence[np.ndarray],
                           mems: Sequence[np.ndarray],
                           n_items: Sequence[int], cfg: GGPUConfig, *,
                           out_regions: Optional[Sequence[Region]] = None,
                           patches=None, mesh=None) -> LaunchHandle:
    """Dispatch N heterogeneous launches as one vmapped stepper call,
    asynchronously (padding exactly as ``run_kernel_batch``). ``patches``
    optionally overwrites regions of the staged memory with device arrays
    before dispatch (see the patch protocol above; bounds check against
    each launch's own memory size, not the padded envelope). ``mesh``
    shards the vmapped launch axis across the mesh's data-parallel
    devices, padding N up to the shard count with trivial 1-item HALT
    fillers (invisible at resolution); a 1-extent mesh falls back to the
    single-device path."""
    if not (len(progs) == len(mems) == len(n_items)):
        raise ValueError("progs, mems, n_items must have equal length")
    if not progs:
        raise ValueError("empty batch")
    progs = [np.asarray(p, np.int32) for p in progs]
    mems = [np.asarray(m, np.int32) for m in mems]
    n_items = [int(n) for n in n_items]
    B = len(progs)
    if patches is not None:
        _check_patches(patches, B, [m.shape[0] for m in mems])
    shards = launch_shards(mesh)
    pad = -B % shards if shards > 1 and B > 1 else 0
    if pad:
        width = progs[0].shape[1]
        progs = progs + [np.zeros((1, width), np.int32)] * pad  # HALT
        mems = mems + [np.zeros(1, np.int32)] * pad
        n_items = n_items + [1] * pad
    P = max(p.shape[0] for p in progs)
    M = max(m.shape[0] for m in mems)
    prog_b = np.stack([np.pad(p, ((0, P - p.shape[0]), (0, 0)))
                       for p in progs])                  # HALT == all-zeros
    # each row zero-padded to the envelope plus its own write-sink slot
    mem_b = np.stack([np.pad(m, (0, M + 1 - m.shape[0])) for m in mems])
    W = max(_n_wavefronts(int(n), cfg) for n in n_items)
    ops = tuple(sorted(set().union(*(_static_ops(p) for p in progs))))
    n_arr = jnp.asarray(np.asarray(n_items, np.int32))
    msz_arr = jnp.asarray(np.array([m.shape[0] for m in mems], np.int32))
    if shards > 1 and B > 1:
        sharding = _launch_sharding(mesh, 2)
        staged = jax.device_put(mem_b, sharding)
        if patches is not None:
            # batch rows are already row-per-launch; patch then reshard
            staged = jax.device_put(_patch_rows(staged, patches), sharding)
        final = _sharded_batch_fn(cfg, W, P, M, ops, mesh)(
            jnp.asarray(prog_b), staged, n_arr, msz_arr)
    else:
        staged = jnp.asarray(mem_b)
        if patches is not None:
            staged = _patch_rows(staged, patches)
        final = _run_batch(jnp.asarray(prog_b), staged, n_arr, msz_arr,
                           cfg, W, P, ops)
    return LaunchHandle(final, cfg, "batch", B, M,
                        [m.shape[0] for m in mems[:B]], out_regions,
                        B, staged, rows=B + pad)


def run_kernel_batch(progs: Sequence[np.ndarray],
                     mems: Sequence[np.ndarray],
                     n_items: Sequence[int],
                     cfg: GGPUConfig) -> List[Tuple[np.ndarray, dict]]:
    """Execute N heterogeneous kernel launches as one vmapped stepper call.

    Programs are padded to a common length with HALT words and memory
    images zero-padded to a common size; per-launch results and cycle
    counts are exact (the padding is invisible to the machine — each
    launch's address clip still binds at its own memory size). Returns a
    list of (mem_final, info) in submission order."""
    progs = list(progs)              # materialize once: iterators welcome
    if not progs:
        return []
    return run_kernel_batch_async(progs, list(mems), list(n_items),
                                  cfg).results()
