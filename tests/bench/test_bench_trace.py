"""The trace reduction: exact on a hand-made trace, and sound on a small
trace recorded on a TPU v5e (``data/chip_trace.xplane.pb.gz``, made by
``make_trace.py`` and gzipped)."""
import gzip
import os
import shutil

import pytest

from bench import trace as tr

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP_TRACE = os.path.join(HERE, "data", "chip_trace.xplane.pb.gz")


def hand_trace():
    dev = {tr.MODULES: [("jit_chunk_fn(17)", 100, 50), ("jit_fn(3)", 200, 30),
                        ("jit_chunk_fn(17)", 310, 80),
                        ("jit_chunk_fn(17)", 500, 10)],     # after the window
           tr.OPS: [("a", 100, 20), ("b", 110, 30), ("c", 200, 30),
                    ("d", 310, 80), ("e", 500, 10)]}
    host = [("bench.step_chunk", 0, 150), ("bench.wait_for_arrival", 160, 130),
            ("bench.submit", 290, 10), ("bench.step_chunk", 300, 100)]
    return tr.Trace({"/device:TPU:0": dev}, host)


def test_busy_idle_and_programs_from_a_hand_made_trace():
    s = tr.summarise(hand_trace())
    assert s.window_s == pytest.approx(400e-9)
    # ops [100,140) [200,230) [310,390): 150 ns busy of 400
    assert s.busy_s == pytest.approx(150e-9)
    assert s.programs == {"chunk_fn": (2, pytest.approx(130e-9)),
                          "fn": (1, pytest.approx(30e-9))}
    # idle gaps, longest first, named by what the host was doing
    assert [(n, round(t * 1e9)) for n, t in s.gaps] == [
        ("bench.step_chunk", 100), ("bench.wait_for_arrival", 80),
        ("bench.wait_for_arrival", 60), ("bench.step_chunk", 10)]
    b = tr.breakdown(s)
    assert b["device_ops"][0][0] == "chunk_fn" and len(b["idle_gaps"]) == 4


def test_no_boundary_or_no_device_reads_nothing():
    t = hand_trace()
    assert tr.summarise(tr.Trace(t.devices, [])) is None
    assert tr.summarise(tr.Trace({}, t.host)) is None


def test_program_names_drop_the_jit_prefix_and_id():
    assert tr.program_name("jit_chunk_fn(1234567)") == "chunk_fn"
    assert tr.program_name("jit__pad(9)") == "_pad"
    assert tr.program_name("fusion.12") == "fusion.12"


def test_reduction_of_a_trace_recorded_on_the_chip(tmp_path):
    path = tmp_path / "chip_trace.xplane.pb"
    with gzip.open(CHIP_TRACE) as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    s = tr.summarise(tr.load(str(path)))
    assert s is not None and s.n_devices == 1
    assert 0 < s.busy_s < s.window_s < 1.0
    assert {"chunk_fn", "fn"} <= set(s.programs)
    runs, secs = s.programs["chunk_fn"]
    assert runs >= 1 and 0 < secs <= s.busy_s
    assert s.gaps and all(n.startswith(("bench.", "host.")) and t > 0
                          for n, t in s.gaps)
    assert [t for _, t in s.gaps] == sorted((t for _, t in s.gaps),
                                            reverse=True)
    b = tr.breakdown(s)
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
