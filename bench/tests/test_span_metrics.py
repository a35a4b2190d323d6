"""The readers of the program's spans and kernel names against a
hand-made trace with known answers: chip idle time under the engine's
prefill and wave host spans, and the retrieval kernels' device time."""
from __future__ import annotations

import pathlib
import sys
import types

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import devtrace  # noqa: E402
import run  # noqa: E402
import spans  # noqa: E402

TPU0, TPU1 = "/device:TPU:0", "/device:TPU:1"

# window [0, 100]. Chip 0 busy [0,12] [20,25] [55,58] [70,95], so idle
# [12,20] [25,55] [58,70] [95,100]; chip 1 busy all through.
TRACE = {
    "/host:CPU": {
        "python": [("bench.window", 0.0, 100.0),
                   ("ralm.prefill", 10.0, 20.0),
                   ("ralm.prefill.scatter", 30.0, 5.0),
                   ("ralm.wave.mix", 50.0, 10.0),
                   ("ralm.wave.sample", 60.0, 5.0),
                   ("ralm.wave.stream", 65.0, 10.0)],
        # another thread; its span runs past the window's end
        "python2": [("ralm.wave.mix", 90.0, 20.0)]},
    TPU0: {devtrace.OPS: [
        ("%chamvs_scan.1 = (f32[1,16,128]) custom-call(%ivf_scan.1)",
         0.0, 12.0),
        # names the scan's result as an operand: not the scan
        ("%fusion.3 = f32[8] fusion(%chamvs_scan.1)", 20.0, 5.0),
        ("ivf_scan.2", 55.0, 3.0),
        ("%while.2 = (s32[]) while(%tuple.1)", 70.0, 25.0)]},
    TPU1: {devtrace.OPS: [("%while.1 = (s32[]) while(%t)", 0.0, 100.0)]},
}


def ctx(trace=TRACE, planes=(TPU0, TPU1), win=(0.0, 100.0)):
    return types.SimpleNamespace(trace=trace, planes=list(planes), win=win)


@pytest.mark.parametrize("name,chip0", [
    # prefill [10,35]: idle [12,20] and [25,35]
    ("prefill_idle_pct", 18.0),
    # wave host work [50,75] and [90,100]: idle [50,55] (part of the gap
    # [25,55]), [58,70], [95,100]
    ("wave_host_idle_pct", 22.0),
    # chamvs_scan [0,12] and ivf_scan [55,58]
    ("scan_busy_pct", 15.0),
])
def test_reader_of_a_hand_made_trace(name, chip0):
    read = run.reader(name)
    assert read(ctx(planes=[TPU0])) == pytest.approx(chip0)
    # the mean over the chips: chip 1 never idles and runs no scan
    assert read(ctx()) == pytest.approx(chip0 / 2)


@pytest.mark.parametrize("name", ["prefill_idle_pct", "wave_host_idle_pct",
                                  "scan_busy_pct"])
def test_reader_finds_nothing_to_read(name):
    """A program without the spans or kernel names (its scan named after
    its jitted function) gives no reading, and nothing raises; neither
    does a trace without a window or a device."""
    read = run.reader(name)
    bare = {"/host:CPU": {"python": [("bench.window", 0.0, 100.0),
                                     ("bench.prefill", 10.0, 20.0)]},
            TPU0: {devtrace.OPS: [("%fused_scan.1 = (f32[1]) custom-call()",
                                   0.0, 12.0)]}}
    assert read(ctx(trace=bare, planes=[TPU0])) is None
    assert read(ctx(win=None)) is None
    assert read(ctx(planes=[])) is None


def test_kernel_of_an_operation_name():
    assert spans.kernel("%chamvs_scan.1 = (f32[2]) custom-call(%x)") == \
        "chamvs_scan"
    assert spans.kernel("ivf_scan.12") == "ivf_scan"
    assert spans.kernel("decode_attn") == "decode_attn"
    assert spans.kernel("%fusion.3 = f32[8] fusion(%chamvs_scan.1)") == \
        "fusion"
