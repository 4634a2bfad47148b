"""The trace reduction on a hand-built trace with known answers."""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import metrics, scopes, xplane  # noqa: E402

MS = 1_000_000


def trace():
    """A 100 ms window: two runs of a decode program (30 ms each) holding a
    kernel and a fusion, one run of a small program, host spans around."""
    host = [("bench_window", 0, 100 * MS),
            ("tick", 0, 45 * MS), ("harvest", 45 * MS, 50 * MS),
            ("feed", 50 * MS, 60 * MS), ("tick", 60 * MS, 100 * MS),
            ("not_a_span", 0, 100 * MS)]
    ops = [("tdvmm_fused_kernel.3", 5 * MS, 15 * MS), ("fusion.1", 15 * MS, 35 * MS),
           ("argmax", 36 * MS, 40 * MS),
           ("tdvmm_fused_kernel.7", 65 * MS, 75 * MS), ("fusion.1", 70 * MS, 95 * MS),
           ("late", 99 * MS, 120 * MS)]
    modules = [("jit__lambda", 5 * MS, 35 * MS), ("jit_argmax", 36 * MS, 40 * MS),
               ("jit__lambda", 65 * MS, 95 * MS)]
    return {"host": host, "devices": {"/device:TPU:0": {"ops": ops, "modules": modules}}}


@pytest.mark.parametrize("extra", [{}, {"/device:TPU:0 idle": {"ops": [], "modules": []}}],
                         ids=["one-plane", "plus-a-plane-without-ops"])
def test_busy_union_idle_and_window(extra):
    tr = trace()
    tr["devices"].update(extra)
    red = xplane.reduce(tr)
    as_scoped = dict(tr, devices={k: dict(v, ops=[(*o, None) for o in v["ops"]])
                                  for k, v in tr["devices"].items()})
    gaps = scopes.reduce(as_scoped, {})["top_gaps"]
    assert [round(g * 1e3) for _, g in gaps] == [25, 5, 4, 1]
    assert red["window_s"] == pytest.approx(0.1)
    # busy: [5,35] + [36,40] + [65,95] + [99,100] = 30 + 4 + 30 + 1 ms
    assert red["busy_s"] == pytest.approx(0.065)
    assert metrics.read("idle_share", {"trace": red}) == pytest.approx(35.0)


def test_op_time_by_name_inside_the_window():
    red = xplane.reduce(trace())
    assert red["ops"]["tdvmm_fused_kernel.3"] == [1, pytest.approx(0.010)]
    assert red["ops"]["fusion.1"] == [2, pytest.approx(0.045)]
    assert "late" not in red["ops"]          # runs past the window's end
    assert red["top_ops"][0] == ["fusion.1", pytest.approx(0.045)]


def test_programs_told_apart_by_run_count():
    red = xplane.reduce(trace())
    assert xplane.program(red["modules"], 2) == ("jit__lambda", 2, pytest.approx(0.060))
    assert xplane.program(red["modules"], 1) == ("jit_argmax", 1, pytest.approx(0.004))
    assert xplane.program(red["modules"], 3) is None
    rec = {"decode_program": xplane.program(red["modules"], 2)}
    assert metrics.read("decode_step_ms", rec) == pytest.approx(30.0)


def test_roofline_reader_takes_only_the_kernel():
    red = xplane.reduce(trace())
    rec = {"trace": red, "least_kernel_s": 0.005, "kernel_launches": 2}
    assert metrics.read("tdvmm_roofline", rec) == pytest.approx(25.0)
    rec = {"trace": dict(red, ops={"fusion.1": [2, 0.045]}),
           "least_kernel_s": 0.0, "kernel_launches": 0}
    assert metrics.read("tdvmm_roofline", rec) is None


@pytest.mark.parametrize("ops", [
    {"fusion.1": [2, 0.045]},                         # the kernel under another name
    {"tdvmm_fused_kernel.3": [1, 0.01]},              # a launch missing
    {"tdvmm_fused_kernel.3": [2, 0.01], "tdvmm_fused_kernel.9": [1, 0.01]},
], ids=["renamed", "missing", "extra"])
def test_roofline_reader_refuses_a_launch_count_it_cannot_explain(ops):
    red = dict(xplane.reduce(trace()), ops=ops)
    with pytest.raises(ValueError, match="launches expected"):
        metrics.read("tdvmm_roofline", {"trace": red, "least_kernel_s": 0.005,
                                        "kernel_launches": 2})


def test_merge_clips_and_joins():
    assert xplane.merge([(0, 5), (3, 8), (10, 12), (11, 20)], 1, 15) == [(1, 8), (10, 15)]


@pytest.mark.parametrize("text, name", [
    ("%tdvmm_fused_kernel.3 = f32[48,3072]{1,0} custom-call(s8[48,1024] %a)",
     "tdvmm_fused_kernel.3"),
    ("%copy.41 = bf16[1,3073,16,16,64]{4,3,2,1,0} copy(bf16[1,3073] %b)", "copy.41"),
    ("fusion.1", "fusion.1"),
])
def test_op_named_by_its_hlo_text(text, name):
    assert xplane.op_name(text) == name

