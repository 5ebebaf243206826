"""The trace reduction: on a synthetic trace whose numbers are worked
by hand, and on a small trace recorded on a TPU v5e (the tiny cell of
``bench/tests/tinycell.py``, served for half a second), against the
engine's own counts of that run."""
import gzip
import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH.parent))

from bench import trace as T  # noqa: E402

DATA = BENCH / "tests" / "data"


def _line(lid, name, base_ns, events):
    evs = "".join(f"events {{ metadata_id: {m} offset_ps: {o * 1000} "
                  f"duration_ps: {d * 1000} }}\n" for m, o, d in events)
    return (f"lines {{ id: {lid} name: {json.dumps(name)} "
            f"timestamp_ns: {base_ns}\n{evs}}}\n")


def _plane(pid, name, lines, names):
    meta = "".join(f"event_metadata {{ key: {i} value {{ id: {i} name: "
                   f"{json.dumps(n)} }} }}\n" for i, n in names.items())
    return f"planes {{ id: {pid} name: {json.dumps(name)}\n{lines}{meta}}}\n"


def synthetic():
    """Window [100, 200) ns.  Chip 0 runs ops over [100, 130), [120, 150)
    (overlapping) and [170, 180): busy 60 ns, gaps [150, 170) and
    [180, 200).  The host dispatches in [148, 171) and reads back in
    [181, 199).  Chip 1 is busy [100, 140)."""
    from jax.profiler import ProfileData

    ops = {1: "%fusion.12 = bf16[8] fusion(x)",
           2: "%paged_decode_attention.3 = bf16[8] custom-call(x)",
           3: "%while.4 = (s32[]) while(t)",
           4: "%fusion.99 = f32[] fusion(y)",
           11: "jit__decode_chunk_impl(123)",
           12: "jit__prefill_slot_impl(456)"}
    tpu0 = (_line(1, "XLA Modules", 0, [(11, 100, 50), (12, 170, 10)])
            + _line(2, "XLA Ops", 0, [(3, 100, 30), (2, 120, 30),
                                      (1, 170, 10), (4, 100, 30)]))
    tpu1 = (_line(1, "XLA Modules", 0, [(11, 100, 40)])
            + _line(2, "XLA Ops", 0, [(2, 100, 40)]))
    host = _line(1, "main", 0, [(1, 100, 100), (2, 148, 23), (3, 181, 18),
                                (4, 10, 5)])
    text = (_plane(1, "/device:TPU:0", tpu0, ops)
            + _plane(2, "/device:TPU:1", tpu1, ops)
            + _plane(3, "/host:CPU", host, {
                1: T.WINDOW, 2: "PjitFunction(_decode_chunk_impl)",
                3: "TransferFromDevice", 4: "before the window"}))
    return ProfileData.from_text_proto(text)


def test_synthetic_trace_by_hand():
    tr = T.load(synthetic())
    assert tr.window == (100.0, 200.0)
    assert tr.window_s == pytest.approx(100e-9)
    # chip 0 busy 60 ns, chip 1 busy 40 ns: 50 ns on average
    assert T.busy_s(tr) == pytest.approx(50e-9)
    assert sorted(T.program_times(tr, "_decode_chunk_impl")) == \
        pytest.approx([40e-9, 50e-9])
    assert T.program_times(tr, "_prefill_slot_impl") == pytest.approx(
        [10e-9])
    # the kernel: 30 ns on chip 0, 40 on chip 1
    assert T.op_seconds(tr, "paged_decode") == pytest.approx(35e-9)
    top = dict(T.top_ops(tr))
    assert "while" not in top
    assert top["paged_decode_attention"] == pytest.approx(35e-9)
    assert top["fusion"] == pytest.approx(20e-9)
    gaps = T.idle_gaps(tr)
    assert gaps == [["PjitFunction(_decode_chunk_impl)",
                     pytest.approx(20e-9)],
                    ["TransferFromDevice", pytest.approx(20e-9)]]
    assert "before the window" not in tr.host.names


@pytest.fixture(scope="module")
def recorded():
    from jax.profiler import ProfileData

    raw = gzip.decompress((DATA / "tiny_v5e.xplane.pb.gz").read_bytes())
    facts = json.loads((DATA / "tiny_v5e.facts.json").read_text())
    return T.load(ProfileData.from_serialized_xspace(raw)), facts


def test_recorded_trace_against_the_engine_counts(recorded):
    tr, facts = recorded
    assert len(tr.ops) == 1
    # one decode-chunk execution per chunk the engine counted, one
    # prefill per request, all inside the window
    assert len(T.program_times(tr, "_decode_chunk_impl")) == \
        facts["stats"]["decode_chunks"]
    assert len(T.program_times(tr, "_prefill_slot_impl")) == \
        facts["requests"]
    assert T.busy_s(tr) == pytest.approx(facts["busy_s"], rel=1e-9)
    assert tr.window_s == pytest.approx(facts["window_s"], rel=1e-9)
    assert 0 < T.busy_s(tr) < tr.window_s
    kernel = T.op_seconds(tr, "paged_decode")
    decode = sum(T.program_times(tr, "_decode_chunk_impl"))
    assert 0 < kernel < decode
    names = [n for n, _ in T.top_ops(tr)]
    assert "paged_decode_attention" in names and "while" not in names
    gaps = T.idle_gaps(tr)
    assert 0 < len(gaps) <= 10
    assert all(0 < s < tr.window_s for _, s in gaps)
    assert [s for _, s in gaps] == sorted((s for _, s in gaps),
                                          reverse=True)
