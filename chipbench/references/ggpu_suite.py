"""Plain reference of the G-GPU benchmark suite: what each kernel computes,
written from the kernels' definitions (the paper's seven AMD OpenCL SDK
micro-benchmarks and the segmented reduction) in NumPy, on a memory image
of 32-bit words. It imports nothing of the program.

Each bench reads its inputs from fixed regions of the image and writes
one output region; ``layout(name, sizes)`` gives that region, and
``expected(name, sizes, mem)`` its contents. Arithmetic is on 32-bit
two's-complement words: products and sums wrap, and integer division
floors, with ``x / 0 == 0``.

``derived_stats(name, sizes, machine, mem)`` counts, from each kernel's
instruction listing (the paper's one work-item per output element), the
statistics the kernel alone fixes: ``instrs`` (instructions issued, one
per wavefront that has a lane at the instruction; divergent paths issue
one after the other, lowest address first), ``mem_ops`` (lanes that load
or store), ``steps`` (lockstep rounds: each compute unit runs its first
``max_wf_per_cu`` unfinished wavefronts, wavefront ``w`` on unit
``w % n_cus``), and, for the benches that touch no cache line twice,
``hits`` 0 and ``misses`` equal to ``mem_ops``. ``li`` of an immediate
outside ``[-2048, 2048)`` takes two instructions.
"""
from __future__ import annotations

import heapq

import numpy as np


def _wrap(x) -> np.ndarray:
    return np.asarray(x, np.int64).astype(np.int32)


def layout(name: str, s: dict) -> tuple:
    """``(mem_words, out_lo, out_hi)`` of bench ``name`` at sizes ``s``."""
    if name == "copy":
        n = s["n"]
        return 2 * n, n, 2 * n
    if name in ("vec_mul", "div_int", "xcorr"):
        n = s["n"]
        return 3 * n, 2 * n, 3 * n
    if name == "mat_mul":
        n2 = s["dim"] ** 2
        return 3 * n2, 2 * n2, 3 * n2
    if name == "fir":
        n, t = s["n"], s["taps"]
        return 2 * n + t, n + t, 2 * n + t
    if name == "parallel_sel":
        n = s["n"]
        return 2 * n, n, 2 * n
    if name == "reduction":
        n, seg = s["n"], s["seg"]
        return 2 * n + n // seg, 2 * n, 2 * n + n // seg
    raise KeyError(f"no reference for bench {name!r}")


def expected(name: str, s: dict, mem: np.ndarray) -> np.ndarray:
    """The output region bench ``name`` must leave in image ``mem``."""
    m = np.asarray(mem, np.int64)
    if name == "copy":
        return _wrap(m[:s["n"]])
    if name == "vec_mul":
        n = s["n"]
        return _wrap(m[:n] * m[n:2 * n])
    if name == "div_int":
        n = s["n"]
        a, b = m[:n], m[n:2 * n]
        return _wrap(np.where(b == 0, 0, a // np.where(b == 0, 1, b)))
    if name == "mat_mul":
        d = s["dim"]
        n2 = d * d
        a, b = m[:n2].reshape(d, d), m[n2:2 * n2].reshape(d, d)
        return _wrap((a @ b).reshape(-1))
    if name == "fir":
        n, t = s["n"], s["taps"]
        x, h = m[:n], m[n:n + t]
        out = np.zeros(n, np.int64)
        for k in range(t):                    # out[i] = sum_k h[k] x[i-k]
            out[k:] += h[k] * x[:n - k]
        return _wrap(out)
    if name == "xcorr":
        n = s["n"]
        a, b = m[:n], m[n:2 * n]
        # out[lag] = sum_i a[i] * b[(i + lag) mod n], in blocks of lags
        out = np.empty(n, np.int64)
        i = np.arange(n)
        for lo in range(0, n, 256):
            lags = np.arange(lo, min(n, lo + 256))
            out[lags] = b[(i[None, :] + lags[:, None]) % n] @ a
        return _wrap(out)
    if name == "parallel_sel":
        return _wrap(np.sort(m[:s["n"]]))
    if name == "reduction":
        n, seg = s["n"], s["seg"]
        return _wrap((m[:n].reshape(-1, seg)
                      * m[n:2 * n].reshape(-1, seg)).sum(axis=1))
    raise KeyError(f"no reference for bench {name!r}")


# -- statistics fixed by the kernel ----------------------------------------

def _li(imm: int) -> int:
    return 1 if -2048 <= imm < 2048 else 2


def _items(name: str, s: dict) -> int:
    if name == "mat_mul":
        return s["dim"] ** 2
    if name == "reduction":
        return s["n"] // s["seg"]
    return s["n"]


def _per_wavefront(name: str, s: dict, lanes: np.ndarray,
                   mem: np.ndarray) -> tuple:
    """Instructions one wavefront issues, and its lanes' memory operations,
    for the wavefront whose valid item indices are ``lanes``."""
    hi = int(lanes[-1])
    k = lanes.size
    if name == "copy":                  # tid lw sw halt
        return 4, 2 * k
    if name in ("vec_mul", "div_int"):  # tid lw lw op sw halt
        return 6, 3 * k
    if name == "mat_mul":
        d = s["dim"]                    # tid srli andi slli li li li(d)
        return 6 + _li(d) + 10 * d + 3, k * (2 * d + 1)
    if name == "reduction":
        seg = s["seg"]                  # tid slli li li li(seg); 8 a round
        return 4 + _li(seg) + 8 * seg + 3, k * (2 * seg + 1)
    if name == "fir":
        t = s["taps"]                   # the 4 of a tap issue if a lane has i >= tap
        taps_live = min(t, hi + 1)
        mem_ops = int(np.minimum(lanes + 1, t).sum()) * 2 + k
        return 3 + _li(t) + 5 * t + 4 * taps_live + 3, mem_ops
    if name == "xcorr":
        n = s["n"]                      # sub issues where a lane wraps
        return 3 + _li(n) + 9 * n + min(hi, n) + 3, k * (2 * n + 1)
    if name == "parallel_sel":
        n = s["n"]
        a = np.asarray(mem[:n], np.int64)
        v = a[lanes][:, None]           # (lanes, 1) against a[j] (1, n)
        ge, eq = a[None, :] >= v, a[None, :] == v
        inc = (a[None, :] < v) | (eq & (np.arange(n)[None, :]
                                         < lanes[:, None]))
        per_j = 5 + ge.any(0).astype(int) + eq.any(0) + inc.any(0)
        return 4 + _li(n) + int(per_j.sum()) + 3, k * (n + 2)
    raise KeyError(f"no statistics for bench {name!r}")


#: benches whose every cache line is touched by one instruction only
STREAMING = ("copy", "vec_mul", "div_int")


def derived_stats(name: str, s: dict, machine: dict,
                  mem: np.ndarray) -> dict:
    """The statistics of one launch of bench ``name`` that its kernel fixes
    (see the module doc)."""
    wf, cus, resident = (machine["wavefront"], machine["n_cus"],
                         machine["max_wf_per_cu"])
    items = _items(name, s)
    counts, mem_ops = [], 0
    for lo in range(0, items, wf):
        c, m = _per_wavefront(name, s, np.arange(lo, min(items, lo + wf)),
                              mem)
        counts.append(c)
        mem_ops += m
    steps = 0
    for cu in range(cus):
        free = [0] * resident           # round at which each slot frees
        for c in counts[cu::cus]:
            start = heapq.heappop(free)
            heapq.heappush(free, start + c)
        steps = max(steps, max(free))
    out = {"instrs": sum(counts), "mem_ops": mem_ops, "steps": steps}
    if name in STREAMING:
        out.update(hits=0, misses=mem_ops)
    return out
