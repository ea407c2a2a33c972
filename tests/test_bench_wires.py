"""The benchmark's reader of the wires (``benchmark/harness/timeline_wires.py``)
and the reducer over it (``benchmark/reducers/wire_share.py``): on small
hand-made timelines, on the fixture cut from a real four-chip trace
(``harness/fixture_wires.json``), through a CPU rehearsal that has no device
plane, and the ten metric files' declarations."""

import contextlib
import io
import json
import os
import sys
import time
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import timeline_wires as tw  # noqa: E402
from benchmark.reducers import wire_share  # noqa: E402

WHATS = ("inflight", "hidden", "ici", "counted", "named")
PEAKS = {"ici_bytes_per_s": 200e9}
SHAPE = "f32[4,8,128]"
NBYTES = 4 * 8 * 128 * 4
MODULE = "jit_step(7)"


def _start(k, pairs="{0,1},{1,0}"):
    name = f"collective-permute-start.{k}"
    return (f"%{name} = ({SHAPE}{{2,1,0}}, {SHAPE}{{2,1,0}}, u32[]{{:S(2)}}, u32[]{{:S(2)}}) "
            f"collective-permute-start({SHAPE}{{2,1,0}} %copy.{k}), channel_id=1, "
            f"source_target_pairs={{{pairs}}}")


def _done(k):
    return (f"%collective-permute-done.{k} = {SHAPE}{{2,1,0}} collective-permute-done(({SHAPE}{{2,1,0}}, "
            f"{SHAPE}{{2,1,0}}, u32[]{{:S(2)}}, u32[]{{:S(2)}}) %collective-permute-start.{k})")


X_LOW = "jit(step)/shard_map/while/body/exchange.x/exchange.x.low/ppermute"
Y_HIGH = "jit(step)/shard_map/while/body/step.stage.2/exchange.y/exchange.y.high/ppermute"
KERNEL = ["custom-call_f32_64_64_64_", "jit(step)/shard_map/while/body/plane_pass/pallas_call"]
UNPACK = ["dynamic-update-slice_f32_64_4_64_", "jit(step)/shard_map/while/body/exchange.x/dynamic_update_slice"]


def _raw(ops, wires, span_bytes=NBYTES, steps=1, window=(0.0, 1000.0)):
    """``ops``: ``[wire's HLO line | [label, scope], start, dur]``; ``wires``:
    {k: the scope of wire k's start and done} -> the wires' raw form."""
    events = []
    for what, start, dur in ops:
        if isinstance(what, str):
            (k,) = [k for k in wires if what in (_start(k), _start(k, "{0,0},{1,1}"), _done(k))]
            events.append([what, start, dur, wires[k]])
        else:
            events.append([what[0], start, dur, what[1]])
    return {
        "ops": {"/device:TPU:0": events},
        "modules": {"/device:TPU:0": [[MODULE, window[0], window[1] - window[0]]]},
        "host": [["domain.step", 0.0, 5.0, {"steps": steps, "wire_bytes": span_bytes, "wired": "x"}]],
    }


def _read(raw):
    """The five shares and the run's ``{"bench": "wires"}`` line."""
    ctx = {"wires": tw.build(raw), "peaks": PEAKS}
    with contextlib.redirect_stdout(io.StringIO()) as out:
        got = {what: wire_share.reduce(ctx, what) for what in WHATS}
    return got, json.loads(out.getvalue()) if out.getvalue() else None


def test_a_wire_followed_at_once_by_its_wait_is_all_exposed():
    ops = [[_start(1), 0.0, 2.0], [_done(1), 2.0, 98.0], [KERNEL, 100.0, 300.0]]
    got, line = _read(_raw(ops, {1: X_LOW}))
    assert got["hidden"] == 0.0 and got["named"] == 100.0 and got["counted"] == 100.0
    assert got["inflight"] == pytest.approx(100.0 * 100 / 400)
    # 16 KiB in 100 ns = 163.84 GB/s of a 200 GB/s peak
    assert got["ici"] == pytest.approx(100.0 * NBYTES / 100e-9 / 200e9) and got["ici"] < 100
    assert (line["hidden_us_a_step"], line["own_us_a_step"], line["exposed_us_a_step"]) == (0.0, 0.0, 0.1)


def test_a_wire_under_a_kernel_from_its_issue_to_its_done_is_hidden():
    """All but the two ops' own time: the issue and the done run on the core
    the kernel runs on."""
    ops = [[_start(1), 0.0, 2.0], [KERNEL, 2.0, 96.0], [_done(1), 98.0, 2.0], [KERNEL, 100.0, 100.0]]
    got, line = _read(_raw(ops, {1: X_LOW}))
    assert got["hidden"] == pytest.approx(96.0) and got["inflight"] == pytest.approx(50.0)
    assert line["hidden_us_a_step"] == pytest.approx(0.096) and line["own_us_a_step"] == 0.0


def test_the_exchanges_own_compute_under_a_flight_hides_nothing():
    """A sweep unpacks its low slab while its high one flies: compute, but
    under an ``exchange.*`` scope -- exchange time still, said apart."""
    ops = [[_start(1), 0.0, 2.0], [UNPACK, 2.0, 60.0], [_done(1), 62.0, 38.0], [KERNEL, 100.0, 100.0]]
    got, line = _read(_raw(ops, {1: X_LOW}))
    assert got["hidden"] == 0.0
    assert line["own_us_a_step"] == pytest.approx(0.060) and line["exposed_us_a_step"] == pytest.approx(0.040)
    (hop,) = line["hops"]
    assert (hop["hop"], hop["own_us_a_step"], hop["hidden_us_a_step"]) == ("x.low", pytest.approx(0.060), 0.0)
    both = [[_start(1), 0.0, 2.0], [UNPACK, 2.0, 30.0], [KERNEL, 32.0, 30.0], [_done(1), 62.0, 38.0]]
    assert _read(_raw(both, {1: X_LOW}))[0]["hidden"] == pytest.approx(30.0)


def test_no_share_passes_100_when_flights_overlap_or_the_trace_cuts_one():
    """Two flights in the air at once (both started before either is waited
    for), a done whose start the trace missed and a start whose done it
    missed: unions, so nothing passes 100, and the cut ones are no flight."""
    ops = [
        [_done(9), 0.0, 50.0],  # its start lies before the trace
        [_start(1), 50.0, 10.0], [_start(2), 60.0, 10.0], [KERNEL, 70.0, 200.0],
        [_done(1), 270.0, 300.0], [KERNEL, 570.0, 30.0], [_done(2), 600.0, 400.0],
        [_start(3), 1000.0, 10.0],  # its done lies behind the trace
    ]
    raw = _raw(ops, {1: X_LOW, 2: Y_HIGH, 3: X_LOW, 9: X_LOW}, span_bytes=2 * NBYTES, window=(0.0, 1010.0))
    flights = tw.build(raw)["devices"]["/device:TPU:0"]["flights"]
    assert [(f["inst"], f["hop"], f["stage"]) for f in flights] == [
        ("collective-permute-start.1", "x.low", None), ("collective-permute-start.2", "y.high", 2)]
    got, line = _read(raw)
    assert all(0.0 <= got[w] <= 100.0 for w in WHATS), got
    assert got["inflight"] == pytest.approx(100.0 * 95 / 101)  # 50 .. 1000, one union
    assert got["hidden"] == pytest.approx(100.0 * 23 / 95) and got["counted"] == 100.0
    assert line["bench"] == "wires" and "sources" not in line  # one source: the pair
    assert [(h["stage"], h["hop"], h["bytes_a_step"]) for h in line["hops"]] == [
        (2, "y.high", NBYTES), (None, "x.low", NBYTES)]
    assert line["program_wire_bytes_a_step"] == line["device_wire_bytes_a_step"] == 2 * NBYTES


def test_the_count_falls_below_100_when_the_program_says_twice_the_bytes():
    ops = [[_start(1), 0.0, 2.0], [_done(1), 2.0, 98.0]]
    assert _read(_raw(ops, {1: X_LOW}))[0]["counted"] == 100.0
    doubled = _read(_raw(ops, {1: X_LOW}, span_bytes=2 * NBYTES))[0]
    assert doubled["counted"] == 50.0 and doubled["ici"] > 100.0  # ... and the share of the peak says so too


def test_a_timeline_with_no_collective_reads_none_not_zero():
    """A one-chip cell, a permute that sends every shard to itself (an
    unsplit mesh axis), a CPU rehearsal's trace with no device plane, no
    trace at all, and a program whose spans say no ``wire_bytes``."""
    assert _read(_raw([[KERNEL, 0.0, 400.0]], {})) == (dict.fromkeys(WHATS), None)
    itself = [[_start(1, "{0,0},{1,1}"), 0.0, 2.0], [_done(1), 2.0, 98.0]]
    assert _read(_raw(itself, {1: X_LOW})) == (dict.fromkeys(WHATS), None)
    no_plane = {"workload": None, "devices": {}, "host": []}
    assert all(wire_share.reduce({"wires": no_plane, "peaks": PEAKS}, w) is None for w in WHATS)
    assert all(wire_share.reduce({"wires": None, "peaks": PEAKS}, w) is None for w in WHATS)
    raw = _raw([[_start(1), 0.0, 2.0], [_done(1), 2.0, 98.0]], {1: X_LOW})
    raw["host"] = [["domain.step", 0.0, 5.0, {"steps": 1}]]  # the parent's jacobi step
    before = _read(raw)[0]
    assert before["ici"] is None and before["counted"] is None and before["named"] == 100.0


def test_a_wire_under_no_direction_scope_is_not_named():
    ops = [[_start(1), 0.0, 2.0], [_done(1), 2.0, 48.0], [_start(2), 50.0, 2.0], [_done(2), 52.0, 48.0]]
    got, _ = _read(_raw(ops, {1: X_LOW, 2: "jit(step)/shard_map/ppermute"}, span_bytes=2 * NBYTES))
    assert got["named"] == 50.0 and got["counted"] == 100.0


def test_result_bytes_reads_the_shapes():
    assert tw.result_bytes(_done(1)) == NBYTES
    both = "%collective-permute-done = (bf16[2,3]{1,0}, s32[5]{0}) collective-permute-done(%x)"
    assert tw.result_bytes(both) == 2 * 3 * 2 + 5 * 4
    assert tw.sends_to_itself(_start(1, "{0,0},{1,1}")) and not tw.sends_to_itself(_start(1))


def test_compact_keeps_the_wires_lines_and_every_ops_scope():
    """``timeline.read_xplane``'s raw contents -> the wires' raw form: a wire
    keeps its whole HLO line, every other op its label, each with the scope
    ``timeline.build`` joins it to -- a compiler-added copy between two
    exchange instructions inherits theirs."""
    scope = "jit(step)/shard_map/exchange.x/exchange.x.low/ppermute"
    copy = "%copy.1 = f32[4,8,128]{2,1,0} copy(f32[4,8,128]{2,1,0} %slice.1)"
    raw = {
        "ops": {"/device:TPU:0": [[copy, 0.0, 5.0], [_start(1), 5.0, 2.0], [_done(1), 7.0, 93.0]]},
        "modules": {"/device:TPU:0": [[MODULE, 0.0, 100.0]]},
        "hlo": {MODULE: {
            "slice.1": {"opcode": "slice", "op_name": "jit(step)/shard_map/exchange.x/slice", "operands": []},
            "copy.1": {"opcode": "copy", "op_name": "", "operands": ["slice.1"]},
            "collective-permute-start.1": {"opcode": "collective-permute-start", "op_name": scope,
                                           "operands": ["copy.1"]},
            "collective-permute-done.1": {"opcode": "collective-permute-done", "op_name": scope,
                                          "operands": ["collective-permute-start.1"]},
        }},
        "host": [["domain.step", 0.0, 5.0, {"steps": 1, "wire_bytes": NBYTES}], ["bench.enqueue", 0.0, 1.0, {}]],
    }
    small = tw.compact(raw)
    assert small["ops"]["/device:TPU:0"] == [
        ["copy_f32_4_8_128_", 0.0, 5.0, scope], [_start(1), 5.0, 2.0, scope], [_done(1), 7.0, 93.0, scope]]
    assert [h[0] for h in small["host"]] == ["domain.step"] and "hlo" not in small
    device = tw.build(small)["devices"]["/device:TPU:0"]
    assert device["own"] == [[0.0, 5.0]] and device["kernel"] == []
    assert [(f["hop"], f["bytes"], f["run"]) for f in device["flights"]] == [("x.low", NBYTES, 0)]


# --- the fixture: cut from a real four-chip trace ------------------------------------


def _fixture():
    """The fixture with its interned names and scopes put back: ``raw`` as
    ``compact`` returns it."""
    with open(os.path.join(ROOT, "benchmark", "harness", "fixture_wires.json")) as f:
        fx = json.load(f)
    names = fx["names"]
    fx["raw"]["ops"] = {
        plane: [[names[i], start, dur, names[j]] for i, start, dur, j in events]
        for plane, events in fx["raw"]["ops"].items()}
    fx["raw"]["modules"] = {
        plane: [[names[i], start, dur] for i, start, dur in events]
        for plane, events in fx["raw"]["modules"].items()}
    return fx


def test_the_fixture_reads_as_the_chip_run_read():
    """Two planes of ``acoustic-so8-1200x4.bulk``'s trace, three whole
    dispatches each: the five shares and the line's split come out as the
    reader had them on the chip machine when the cut was made."""
    fx = _fixture()
    tl = tw.build(fx["raw"], fx["workload"])
    assert sorted(tl["devices"]) == ["/device:TPU:0", "/device:TPU:1"]
    ctx = {"wires": tl, "peaks": PEAKS}
    with contextlib.redirect_stdout(io.StringIO()) as out:
        got = {what: wire_share.reduce(ctx, what) for what in WHATS}
    assert got["named"] == 100.0 and got["counted"] == 100.0
    assert 0.0 <= got["hidden"] < 100.0 and 0.0 < got["ici"] < 100.0
    for what, value in fx["read"].items():  # what this cut read when it was made
        assert got[what] == pytest.approx(value, rel=1e-9), what
    # a wait lies inside its flight: never under the collective ops' own share of busy
    import re

    for plane, d in tl["devices"].items():
        ops = fx["raw"]["ops"][plane]
        waits = tw.merged([o[1], o[1] + o[2]] for o in ops if re.search(r" collective-permute-(start|done)\(", o[0]))
        flying = tw.merged([f["start"], f["end"]] for f in d["flights"])
        assert tw.span_ns(tw.overlap(flying, d["busy"])) >= tw.span_ns(waits) - 1e-6
        assert d["own"] and d["kernel"] and not tw.overlap(d["own"], d["kernel"])
    line = json.loads(out.getvalue().splitlines()[0])
    assert sorted(h["hop"] for h in line["hops"]) == ["x.high", "x.low", "y.high", "y.low"]
    assert line["program_wire_bytes_a_step"] == line["device_wire_bytes_a_step"] == 23_658_496
    for part in ("inflight", "hidden", "own", "exposed"):
        assert line[f"{part}_us_a_step"] == pytest.approx(fx["line"][f"{part}_us_a_step"], rel=1e-9), part
    assert line["inflight_us_a_step"] == pytest.approx(
        line["hidden_us_a_step"] + line["own_us_a_step"] + line["exposed_us_a_step"])


# --- a CPU rehearsal, and the declarations -----------------------------------------


def test_a_rehearsal_has_no_device_plane_and_reports_none_of_the_five(tmp_path, monkeypatch):
    """``weak-r3-512x4.exchange-only`` traced on four CPU devices, in a trace
    root of its own: the reader finds the program's ``domain.exchange`` spans
    and their ``wire_bytes``, no device plane, and every new metric reads
    nothing -- as the named shares do."""
    from benchmark.harness import timeline, window

    monkeypatch.setattr(window, "OUT", str(tmp_path / ".bench_out"))
    monkeypatch.setattr(timeline, "TRACE_ROOT", str(tmp_path / ".bench_out" / "trace"))
    opts = types.SimpleNamespace(
        workload="weak-r3-512x4.exchange-only", seed=2**31 + 49, seconds=0.2, trace=1,
        lower_precision=False, describe_trace=False, also_verify=[], rehearse=8, dispatch_size=2)
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        assert window.run(opts, time.perf_counter()) == 0
    lines = [json.loads(x) for x in out.getvalue().splitlines() if x.startswith("{")]
    assert lines[-1]["rehearsal"]["checks_ok"] is True
    assert not [n for n in lines[-1]["rehearsal"]["would_report"] if n.startswith("wire_")]
    assert not [x for x in lines if x.get("bench") == "wires"]
    tl = tw.load()
    assert tl["workload"] == opts.workload and tl["devices"] == {}
    spans = [h[3] for h in tl["host"] if h[0] == "domain.exchange"]
    raw = 8 + 2 * 3  # radius 3 around 8^3 a chip; x and y wired on mesh [2,2,1], four f32 quantities
    # four faces and, the pair flying jointly, the two corner relays behind the y faces
    nbytes = (2 * 2 * 3 * raw * raw + 2 * 6 * 3 * raw) * 4 * 4
    assert spans and {int(a["wire_bytes"]) for a in spans} == {nbytes}
    assert {a["joint"] for a in spans} == {"xy"}
    assert tw.said_bytes(tl) == (nbytes, 1)


def test_the_ten_metrics_are_declared_for_the_four_chip_cells_alone():
    """Each metric file names its cells by PATTERN (``*x4.bulk`` /
    ``*x4.exchange-only``), so the next four-chip cell gets the five shares
    with no new file; ``BENCHMARK.json`` lists by name the cells the patterns
    match today."""
    import fnmatch

    from benchmark.harness.window import layer_metrics_for

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    declared = {m["name"]: m for m in bench["per_layer"]}
    chips = {w["name"]: w["chips"] for w in bench["workloads"]}
    names = [f"wire_{what}_pct.{s}" for what in WHATS for s in ("bulk4", "exch4")]
    order = [m["name"] for m in bench["per_layer"]]
    assert order[order.index(names[0]):][: len(names)] == names  # one block, appended behind what was there
    for name in names:
        with open(os.path.join(ROOT, "benchmark", "layer_metrics", name + ".json")) as f:
            m = json.load(f)
        pattern, moves = (("*x4.bulk", "mcells_per_s_chip") if name.endswith("bulk4")
                          else ("*x4.exchange-only", "halo_gbps_chip"))
        assert (m["cells"], m["moves"], m["layer"], m["unit"]) == ([pattern], moves, "exchange", "%")
        assert m["reducer"] == "wire_share" and m["args"] == {"what": name.split("_")[1]}
        matched = [c for c in chips if fnmatch.fnmatchcase(c, pattern)]
        assert matched and all(chips[c] == 4 for c in matched)
        assert declared[name] == {
            "name": name, "unit": "%", "better": m["better"], "source": m["source"],
            "layer": "exchange", "moves": moves, "workloads": matched}
    for cell, n in chips.items():
        e2e = "halo_gbps_chip" if cell.endswith("exchange-only") else "mcells_per_s_chip"
        mine = {m["name"] for m in layer_metrics_for(cell, {e2e, "setup_s"})}
        assert len(mine & set(names)) == (5 if n == 4 else 0), cell
    with open(os.path.join(ROOT, "benchmark", "harness", "peaks.json")) as f:
        assert {v["ici_bytes_per_s"] for k, v in json.load(f).items() if not k.startswith("_")} == {200e9}
