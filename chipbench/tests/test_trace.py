"""The trace reduction, on a trace recorded on a TPU v5e (``TPU v5 lite``).

``data/paper-k16-round.xplane.pb.gz`` is one round of a ``--trace 1``
run at the published width, with 16 services a round and deadlines of
35-99 ms, cut out of the run's ``.xplane.pb``: the events that lie inside the round's
``chipbench.round`` span, with the host's events other than the
harness's spans and the device's ``Async XLA Ops`` line left out.
"""

import dataclasses
import gzip
import shutil
from pathlib import Path

import pytest

from chipbench import spec, trace, yardstick
from repro.configs.ddim_cifar10 import CONFIG

DATA = Path(__file__).resolve().parent / "data" / \
    "paper-k16-round.xplane.pb.gz"


@pytest.fixture(scope="module")
def summary(tmp_path_factory):
    d = tmp_path_factory.mktemp("trace")
    with gzip.open(DATA) as src, open(d / "round.xplane.pb", "wb") as dst:
        shutil.copyfileobj(src, dst)
    return trace.reduce(trace.load(d))


def test_window_and_busy_time(summary):
    assert summary.chips == 1
    assert 0 < summary.busy_s < summary.window_s
    idle = sum(summary.gap_s.values())
    assert idle + summary.busy_s == pytest.approx(summary.window_s,
                                                  rel=1e-6)


def test_kernel_calls_are_found_with_their_shapes(summary):
    gn = list(spec.metric_module("gn_silu_roofline").calls(summary.pallas))
    # the GroupNorm+SiLU kernel is the only Pallas kernel on the path
    assert gn and len(gn) == len(summary.pallas)
    for rows, hw, c, seconds in gn:
        assert rows in (2, 4, 8, 16) and seconds > 0
    # 45 kernel calls per U-Net step
    assert len(gn) % 45 == 0
    # and they are the calls the yardstick counts from the shapes
    shapes = yardstick.gn_silu_shapes(dataclasses.asdict(CONFIG))
    assert {(hw, c) for _, hw, c, _ in gn} == {
        (h * w, c) for h, w, c in shapes}
    assert 0 < sum(s for *_, s in gn) < summary.busy_s


def test_gaps_are_labelled_by_harness_spans(summary):
    for label, seconds in summary.gap_s.items():
        assert seconds > 0
        assert label == "outside" or label.startswith("chipbench.")
    assert {"chipbench.round", "chipbench.batch",
            "chipbench.open_session"} <= set(summary.gap_s)


def test_breakdown_shape(summary):
    b = summary.breakdown()
    assert set(b) == {"device_ops", "idle_gaps"}
    for key in b:
        assert 0 < len(b[key]) <= 10
        for name, seconds in b[key]:
            assert isinstance(name, str) and seconds > 0
    ops = [s for _, s in b["device_ops"]]
    assert ops == sorted(ops, reverse=True)


def test_span_lookup():
    spans = [("chipbench.round", 0, 100), ("chipbench.batch", 10, 20),
             ("chipbench.batch", 40, 50), ("chipbench.round", 200, 300)]
    look = trace._Spans(spans)
    assert look.label(5) == "chipbench.round"
    assert look.label(15) == "chipbench.batch"
    assert look.label(30) == "chipbench.round>chipbench.batch"
    assert look.label(150) == "outside"
    assert look.label(250) == "chipbench.round"


def test_op_key():
    name = ('%f.45 = f32[16,1024,128]{2,1,0:T(8,128)S(1)} custom-call('
            'f32[16,1024,128]{2,1,0} %bitcast.656), '
            'custom_call_target="tpu_custom_call"')
    assert trace._op_key(name) == "f.45 f32[16,1024,128] pallas"
    assert trace._op_key("%fusion.3 = bf16[2,4]{1,0} fusion(x)") == \
        "fusion.3 bf16[2,4]"
