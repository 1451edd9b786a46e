"""The trace reduction on a small trace built to the profiler's schema: two
chips with module and operation lines, and a host line with the harness's
spans."""
from __future__ import annotations

import pytest

from chipbench import trace

# times in microseconds from the trace's start; one op per module here
CHIP_EVENTS = {
    0: [("jit__run_cohort(1)", 10, 40), ("jit_decode_step(2)", 60, 70)],
    1: [("jit__run_cohort(1)", 12, 50)],
}
SPANS = [("chipbench.window", 0, 100), ("chipbench.dispatch", 5, 12),
         ("chipbench.collect", 12, 90), ("chipbench.wait", 90, 100)]


def _plane(pid: int, name: str, lines) -> str:
    meta, body = {}, []
    for lid, (lname, events) in enumerate(lines, 1):
        evs = []
        for ename, s, e in events:
            mid = meta.setdefault(ename, len(meta) + 1)
            evs.append(f"events {{ metadata_id: {mid} offset_ps: "
                       f"{s * 1_000_000} duration_ps: {(e - s) * 1_000_000} }}")
        body.append(f'lines {{ id: {lid} name: "{lname}" timestamp_ns: 0 '
                    + " ".join(evs) + " }")
    metas = " ".join(f'event_metadata {{ key: {i} value {{ id: {i} name: '
                     f'"{n}" }} }}' for n, i in meta.items())
    return f'planes {{ id: {pid} name: "{name}" ' + " ".join(body) \
        + f" {metas} }}"


def xspace_bytes() -> bytes:
    import jax
    planes = [_plane(10 + c, f"/device:TPU:{c}",
                     [("XLA Modules", evs),
                      ("XLA Ops", [(f"%op.{n}", s, e) for n, s, e in evs])])
              for c, evs in CHIP_EVENTS.items()]
    planes.append(_plane(1, "/host:CPU", [("python", SPANS)]))
    return jax.profiler.ProfileData.text_proto_to_serialized_xspace(
        "\n".join(planes))


@pytest.fixture
def reduced(tmp_path):
    run_dir = tmp_path / "plugins" / "profile" / "1"
    run_dir.mkdir(parents=True)
    (run_dir / "host.xplane.pb").write_bytes(xspace_bytes())
    return trace.reduce(tmp_path)


def test_window_and_busy_share_from_device_lines(reduced):
    assert reduced.window_s == pytest.approx(100e-6)
    # chip 0 busy 30 + 10 us, chip 1 busy 38 us: mean 39 us
    assert reduced.busy_s == pytest.approx(39e-6)
    assert set(reduced.chips) == {"/device:TPU:0", "/device:TPU:1"}


def test_module_seconds_are_averaged_over_chips(reduced):
    sec, n = reduced.seconds(
        "modules", lambda name: name.startswith("jit__run_cohort"))
    assert sec == pytest.approx((30 + 38) / 2 * 1e-6)
    assert n == 1
    sec, n = reduced.seconds("modules",
                             lambda name: name.startswith("jit_decode"))
    assert (sec, n) == (pytest.approx(5e-6), 0.5)


def test_idle_gaps_are_named_by_the_host_span(reduced):
    # chip 0 is idle over [0,10) in dispatch, and [40,60) and [70,100),
    # whose middles fall in collect; longest first
    assert reduced.breakdown()["idle_gaps"] == [
        ["collect", pytest.approx(30e-6)], ["collect", pytest.approx(20e-6)],
        ["dispatch", pytest.approx(10e-6)]]


def test_device_ops_breakdown_lists_the_longest_first(reduced):
    ops = reduced.breakdown()["device_ops"]
    assert ops[0][0] == "%op.jit__run_cohort(1)"
    assert ops[0][1] == pytest.approx((30 + 38) / 2 * 1e-6)
    assert len(ops) <= 10


def test_events_outside_the_window_are_dropped():
    chips = {"/device:TPU:0": {"modules": [("a", 0, 50), ("b", 150, 160)],
                               "ops": []}}
    r = trace.reduce_events(chips, [("chipbench.window", 20, 100)])
    assert r.chips["/device:TPU:0"]["modules"] == [("a", 20, 50)]
    assert r.busy_s == pytest.approx(30e-9)


def test_union_and_gaps():
    assert trace.union_ns([(0, 10), (5, 20), (30, 40)]) == 30
    assert trace.gaps_ns([(0, 10), (5, 20), (30, 40)], 0, 50) == \
        [(20, 30), (40, 50)]


def test_a_trace_without_a_window_span_is_refused():
    with pytest.raises(RuntimeError, match="window"):
        trace.reduce_events({}, [("chipbench.collect", 0, 1)])
