"""flash_attention_roofline: the flash-attention kernel's least possible
time over its traced time, in percent.

Each traced call is read from the trace's operation text, ``%flash_attention
... = <dt>[BH,Sq,hd] custom-call(<dt>[BH,Sq,hd] q, <dt>[BHkv,Skv,hd] k,
<dt>[BHkv,Skv,hd] v)``. A causal call needs ``4 * hd`` FLOPs for each
(query, key) pair with key <= query, ``Sq*(Sq+1)/2`` pairs per head when
``Sq == Skv``, and must read q, k, v and write the output once. Its least
time is the larger of FLOPs over the chip's bf16 peak and bytes over its
memory bandwidth; the prefill calls here are bound by FLOPs.
"""
import re

OP = re.compile(r"^%flash_attention(\.\d+)? = ")
SHAPE = re.compile(r"(bf16|f32|f16)\[(\d+),(\d+),(\d+)\]")
BYTES = {"bf16": 2, "f16": 2, "f32": 4}


def call_cost(text: str, causal: bool = True):
    """(flops, bytes) of one call from its operation text."""
    shapes = SHAPE.findall(text)
    (dt, bh, sq, hd), (_, _, _, _), (kdt, bhkv, skv, _) = \
        [(s[0], *map(int, s[1:])) for s in shapes[:3]]
    pairs = sq * (sq + 1) // 2 if causal and sq == skv else sq * skv
    flops = 4 * hd * bh * pairs
    nbytes = BYTES[dt] * 2 * bh * sq * hd + BYTES[kdt] * 2 * bhkv * skv * hd
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
