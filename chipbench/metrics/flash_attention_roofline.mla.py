"""flash_attention_roofline.mla: the flash-attention kernel's least
possible time over its traced time, in percent, for calls whose value
heads may be narrower than their query and key heads (MLA: 128 beside
192).

Each traced call is read from the trace's operation text, ``%flash_attention
... = <dt>[BH,Sq,hd_v] custom-call(<dt>[BH,Sq,hd] q, <dt>[BHkv,Skv,hd] k,
<dt>[BHkv,Skv,hd_v] v)``. A causal call needs ``2 * (hd + hd_v)`` FLOPs for
each (query, key) pair with key <= query, ``Sq*(Sq+1)/2`` pairs per head
when ``Sq == Skv``, and must read q, k, v and write the output once, each at
its own head size. Its least time is the larger of FLOPs over the chip's
bf16 peak and bytes over its memory bandwidth.
"""
import re

OP = re.compile(r"^%flash_attention(\.\d+)? = ")
SHAPE = re.compile(r"(bf16|f32|f16)\[(\d+),(\d+),(\d+)\]")
BYTES = {"bf16": 2, "f16": 2, "f32": 4}


def call_cost(text: str, causal: bool = True):
    """(flops, bytes) of one call from its operation text."""
    (odt, _, _, _), (qdt, bh, sq, hd), (kdt, bhkv, skv, _), \
        (vdt, _, _, hd_v) = [(s[0], *map(int, s[1:]))
                             for s in SHAPE.findall(text)[:4]]
    pairs = sq * (sq + 1) // 2 if causal and sq == skv else sq * skv
    flops = 2 * (hd + hd_v) * bh * pairs
    nbytes = (BYTES[qdt] * bh * sq * hd + BYTES[odt] * bh * sq * hd_v
              + BYTES[kdt] * bhkv * skv * hd + BYTES[vdt] * bhkv * skv * hd_v)
    return flops, nbytes


def least_seconds(text: str, peaks: dict) -> float:
    flops, nbytes = call_cost(text)
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])


def read(run):
    if run.trace is None:
        return None
    calls = run.trace.events("ops", lambda name: bool(OP.match(name)))
    peaks = run.peaks()
    least = spent = 0.0
    for evs in calls.values():
        for text, s, e in evs:
            least += least_seconds(text, peaks)
            spent += (e - s) / 1e9
    return 100.0 * least / spent if spent else None
