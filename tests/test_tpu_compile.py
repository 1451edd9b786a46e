"""Compiles of the main path for a described TPU v5e (2x2 topology): the
simulator's cohort stepper at paper size, its sharded form on a 4-chip
mesh, the Pallas kernels at real model widths, and the served models'
decode steps at their cells' widths and shapes. Nothing runs — the
TPU compiler refuses here what it would refuse on the chip (a lowering it
lacks, a block that overflows fast memory), at no chip time.

The topology is described inside a module-scoped fixture, never while a
module is imported: only the test worker given this file loads the TPU
compiler library, and every worker collects the same tests. The
persistent compilation cache is off around these compiles (an entry
written for a described chip cannot be read back without one)."""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.configs import get_config
from repro.ggpu import programs
from repro.ggpu.engine import GGPUConfig
from repro.ggpu.engine import stepper
from repro.kernels.flash_attention import flash_attention
from repro.kernels.rglru_scan import rglru_scan
from repro.models import model as M
from repro.models.schema import schema, served_abstract_params
from repro.serve import Engine, EngineConfig

COLLECTIVES = ("all-reduce", "all-gather", "all-to-all", "reduce-scatter",
               "collective-permute")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:          # no TPU compiler in this install
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was_on)
        cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shape(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("memsys", ["shared", "banked"])
def test_cohort_stepper_compiles_at_paper_size(one_chip, memsys):
    """xcorr at its Table III size, 8 CUs, a cohort of 4."""
    b = programs.all_benches()["xcorr"]
    cfg = GGPUConfig(n_cus=8, memsys=memsys)
    B = 4
    compiled = stepper._run_cohort.lower(
        _shape(b.gpu_prog.shape, jnp.int32, one_chip),
        _shape((B * b.gpu_mem.shape[0] + 1,), jnp.int32, one_chip),
        _shape((), jnp.int32, one_chip),
        cfg=cfg, B=B, W=stepper._n_wavefronts(b.gpu_items, cfg),
        prog_len=b.gpu_prog.shape[0],
        ops=stepper._static_ops(b.gpu_prog)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 2**30


def test_sharded_cohort_stepper_compiles_without_collectives(topo):
    """Each chip steps its own slice of the launches: the 4-way sharded
    stepper must hold no cross-chip collective."""
    b = programs.all_benches()["xcorr"]
    cfg = GGPUConfig(n_cus=8)
    mesh = jax.sharding.Mesh(np.asarray(topo.devices), ("data",))
    fn = stepper._sharded_cohort_fn(
        cfg, 4, stepper._n_wavefronts(b.gpu_items, cfg), b.gpu_prog.shape[0],
        b.gpu_mem.shape[0], stepper._static_ops(b.gpu_prog), mesh)
    replicated = NamedSharding(mesh, PartitionSpec())
    rows = NamedSharding(mesh, PartitionSpec("data", None))
    text = fn.lower(
        _shape(b.gpu_prog.shape, jnp.int32, replicated),
        _shape((4, 4 * b.gpu_mem.shape[0] + 1), jnp.int32, rows),
        _shape((), jnp.int32, replicated)).compile().as_text()
    assert not [c for c in COLLECTIVES if c in text]


@pytest.mark.parametrize("seq", [128, 2048])
def test_flash_attention_compiles_at_smollm_widths(one_chip, seq):
    """smollm-360m prefill: 15 query / 5 kv heads of width 64, batch 4."""
    q = _shape((4 * 15, seq, 64), jnp.bfloat16, one_chip)
    kv = _shape((4 * 5, seq, 64), jnp.bfloat16, one_chip)
    text = flash_attention.lower(q, kv, kv).compile().as_text()
    assert "tpu_custom_call" in text


def test_flash_attention_compiles_at_mla_widths(one_chip):
    """DeepSeek-V2-Lite prefill: 16 heads, 192-wide queries and keys beside
    128-wide values, batch 2 of 4,096 tokens."""
    q = _shape((2 * 16, 4096, 192), jnp.bfloat16, one_chip)
    v = _shape((2 * 16, 4096, 128), jnp.bfloat16, one_chip)
    compiled = flash_attention.lower(q, q, v).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.out_info.shape == (2 * 16, 4096, 128)


@pytest.mark.parametrize("seq", [2048, 8192])
def test_rglru_scan_compiles_at_recurrentgemma_width(one_chip, seq):
    """recurrentgemma-2b's RG-LRU width (2560); the sequence streams
    through fast memory one chunk at a time, so 8192 fits as 2048 does."""
    x = _shape((1, seq, 2560), jnp.float32, one_chip)
    h0 = _shape((1, 2560), jnp.float32, one_chip)
    text = rglru_scan.lower(x, x, h0).compile().as_text()
    assert "tpu_custom_call" in text


# The served models' configs at their cells' widths and precision, a few
# layers deep (the layer scan's body is the same at any depth); decode
# batch 16, prompt + new tokens + 1 positions, as in the benchmark's cells.
DECODE_CELLS = {
    "smollm-360m": (dict(n_layers=3, use_pallas=True), 1024 + 256 + 1),
    "deepseek-v2-lite": (dict(n_layers=4, experts_held=8, use_pallas=True,
                              param_dtype="bfloat16"), 4096 + 256 + 1),
}


def _scheduled(text):
    """(op, element count, line) of each instruction of the entry and the
    loop bodies: the computations that run, not those fused into others."""
    bodies = set(re.findall(r"body=%?([\w.\-]+)", text))
    out, keep = [], False
    for line in text.splitlines():
        head = re.match(r"^(ENTRY )?%?([\w.\-]+) ", line)
        if head and not line.startswith(" "):
            keep = bool(head.group(1)) or head.group(2) in bodies
        m = re.match(r"\s+(?:ROOT )?%?[\w.\-]+ = \w+\[([\d,]*)\]\S*\s+"
                     r"([\w\-]+)\(", line)
        if keep and m:
            out.append((m.group(2),
                        int(np.prod([int(d) for d in m.group(1).split(",")
                                     if d])), line))
    return out


@pytest.fixture(scope="module")
def decode_step(one_chip):
    """``get(arch) -> (cfg, served abstract params, cache, compiled)``: the
    engine's decode step compiled for the chip from the tree the engine
    serves, once per arch for the tests that read it."""
    done = {}

    def put(tree):
        return jax.tree.map(lambda a: _shape(a.shape, a.dtype, one_chip),
                            tree)

    def get(arch):
        if arch not in done:
            over, cap = DECODE_CELLS[arch]
            cfg = get_config(arch).replace(**over)
            params = served_abstract_params(cfg)
            cache = put(jax.eval_shape(lambda: M.init_cache(cfg, 16, cap)))
            engine = Engine(cfg, None, EngineConfig(slots=16))
            done[arch] = (cfg, params, cache, engine.decode_fn.lower(
                put(params), cache, _shape((16, 1), jnp.int32, one_chip),
                _shape((), jnp.int32, one_chip)).compile())
        return done[arch]
    return get


@pytest.mark.parametrize("arch", sorted(DECODE_CELLS))
def test_decode_step_updates_the_cache_in_place(decode_step, arch):
    """The engine's decode step, compiled for the chip: the donated cache
    aliases the output, and no copy, slice or fusion the size of a layer's
    cache or of a whole stack runs, in the loop or around it; the only ops
    of that size are the in-place writes of each step's rows."""
    _, _, cache, compiled = decode_step(arch)
    leaves = jax.tree.leaves(cache)
    assert compiled.memory_analysis().alias_size_in_bytes >= sum(
        a.size * a.dtype.itemsize for a in leaves)
    sizes = {n for a in leaves for n in (a.size, a.size // a.shape[0])}
    # copy-start/copy-done into memory space S(1) prefetch a small buffer
    # into the chip's fast memory; a relayout or a private copy is a copy
    big = [line.strip()[:160] for op, n, line in _scheduled(compiled.as_text())
           if n in sizes and op not in ("parameter", "get-tuple-element",
                                        "bitcast", "dynamic-update-slice")
           and not (op in ("copy-start", "copy-done") and "S(1)" in line)]
    assert not big, big


@pytest.mark.parametrize("arch", sorted(DECODE_CELLS))
def test_decode_step_converts_no_served_weight(decode_step, arch):
    """The engine serves every weight the forward reads only in compute
    dtype already in it: its decode step, compiled for the chip, makes no
    array the size of such a leaf, or of a layer's slice of one, in
    another dtype than the leaf's (a float32 tree converts each whole
    stack there, on every step)."""
    cfg, params, _, compiled = decode_step(arch)
    cdt = jnp.dtype(cfg.compute_dtype)
    hlo_cdt = {"bfloat16": "bf16", "float32": "f32"}[cdt.name]
    sizes = set()
    for (path, a), spec in zip(jax.tree_util.tree_leaves_with_path(params),
                               jax.tree.leaves(schema(cfg))):
        if spec.compute_read:
            assert a.dtype == cdt
            sizes.add(a.size)
            if path[0].key == "groups":
                sizes.add(a.size // a.shape[0])
    converts = [line.strip()[:160]
                for op, n, line in _scheduled(compiled.as_text())
                if n in sizes and op not in ("parameter", "get-tuple-element")
                and re.search(r"= (\w+)\[", line).group(1) != hlo_cdt]
    assert not converts, converts
