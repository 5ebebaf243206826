"""The reduction of the engine's host spans: on a synthetic trace whose
numbers are worked by hand, on the recorded TPU v5e trace (a program
without engine spans), and on a tiny cell served on the CPU."""
import gzip
import json
import sys
import time
import types
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, spec  # noqa: E402
from bench import spans as S  # noqa: E402
from bench import trace as T  # noqa: E402
from bench.tests import tinycell  # noqa: E402
from bench.tests.test_bench_trace import _line, _plane  # noqa: E402
from bench.tools import idle_by_span  # noqa: E402

DATA = BENCH / "tests" / "data"
NEW = ("queue_wait_p90_ms", "host_ms_per_admission", "host_ms_per_chunk")


def _reader(name):
    return spec.load_module(BENCH / "metrics" / f"{name}.py",
                            f"bench_metric_{name}").read


HOST = [  # (name, start, duration) ns; the window is [100, 1100)
    (T.WINDOW, 100, 1000),
    (S.STEP, 10, 80), (S.ADMIT, 20, 40),          # before the window
    (S.STEP, 105, 395),
    (S.ADMIT, 110, 90), (S.FIRST_TOKEN, 150, 40),
    (S.ADMIT, 200, 60), (S.FIRST_TOKEN, 240, 10),
    (S.DECODE, 270, 180), ("PjitFunction(_decode_chunk_impl)", 272, 8),
    (S.DECODE_SYNC, 300, 140), (S.STITCH, 450, 30),
    (S.STEP, 500, 200), (S.WAIT_ARRIVAL, 510, 180),
    (S.STEP, 700, 450),                           # clipped at 1100
    (S.PREFILL_CHUNK, 710, 50),
    (S.DECODE, 770, 230), (S.DECODE_SYNC, 800, 190), (S.STITCH, 1000, 50),
]
OPS = [(110, 40), (200, 45), (280, 160), (710, 50), (770, 215)]


def synthetic():
    """Three loop iterations in the window [100, 1100).  The first
    admits two requests (each with its first token's read) and decodes
    a chunk; the second sleeps for an arrival; the third runs a prefill
    chunk and a decode chunk and outlasts the window.  The chip runs ops
    over [110, 150), [200, 245), [280, 440), [710, 760) and [770, 985)."""
    from jax.profiler import ProfileData

    names = {i + 1: n for i, n in enumerate(dict.fromkeys(
        n for n, _, _ in HOST))}
    ids = {n: i for i, n in names.items()}
    host = _line(1, "python", 0, [(ids[n], s, d) for n, s, d in HOST])
    ops = _line(2, "XLA Ops", 0, [(1, s, d) for s, d in OPS])
    text = (_plane(1, "/device:TPU:0", ops,
                   {1: "%fusion.1 = bf16[8] fusion(x)"})
            + _plane(2, "/host:CPU", host, names))
    return T.load(ProfileData.from_text_proto(text))


def test_self_times_readers_and_idle_rows_by_hand():
    tr = synthetic()
    assert len(S.spans(tr, S.ADMIT)) == 2
    assert len(S.spans(tr, S.STEP)) == 3
    assert S.spans(tr, S.STEP)[-1] == (700.0, 1100.0)
    # admissions: 90 - 40 and 60 - 10 ns outside their first tokens
    assert S.self_s(tr, S.ADMIT, (S.FIRST_TOKEN,)) == pytest.approx(100e-9)
    # steps outside admit, prefill chunk, decode sync and the sleep:
    # (395 - 90 - 60 - 140) + (200 - 180) + (400 - 50 - 190)
    excl = (S.ADMIT, S.PREFILL_CHUNK, S.DECODE_SYNC, S.WAIT_ARRIVAL)
    assert S.self_s(tr, S.STEP, excl) == pytest.approx(285e-9)
    ctx = types.SimpleNamespace(trace=tr, done=[])
    assert _reader("host_ms_per_admission")(ctx) == pytest.approx(50e-6)
    assert _reader("host_ms_per_chunk")(ctx) == pytest.approx(142.5e-6)
    # idle [100, 105) lies before the first step; the rest by hand
    want = {S.OUTSIDE: 5, S.STEP: 125, S.FIRST_TOKEN: 45, S.ADMIT: 20,
            S.DECODE: 30, S.DECODE_SYNC: 5, S.STITCH: 80,
            S.WAIT_ARRIVAL: 180}
    got = S.idle_by_span(tr)
    assert got == pytest.approx({n: v * 1e-9 for n, v in want.items()})
    assert sum(got.values()) == pytest.approx(
        tr.window_s - T.busy_s(tr))
    rows = idle_by_span.rows(tr)
    assert rows[S.STEP]["count"] == 3 and rows[S.DECODE_SYNC]["count"] == 2
    # self times: every piece of the spans' union given to one span
    assert rows[S.STEP]["self_s"] == pytest.approx(
        (5 + 10 + 20 + 20 + 10 + 10 + 50) * 1e-9)
    assert rows[S.DECODE]["self_s"] == pytest.approx(80e-9)
    assert sum(r["self_s"] for r in rows.values()) == pytest.approx(
        (395 + 200 + 400) * 1e-9)


def test_queue_wait_reader():
    read = _reader("queue_wait_p90_ms")
    done = [types.SimpleNamespace(arrival_s=1.0, admitted_s=1.0 + 0.1 * i)
            for i in range(10)]
    assert read(types.SimpleNamespace(done=done)) == pytest.approx(810.0)
    # requests of a program that stamps no admission
    old = [types.SimpleNamespace(arrival_s=0.0) for _ in range(3)]
    assert read(types.SimpleNamespace(done=old)) is None


def test_recorded_trace_without_engine_spans_reads_nothing():
    from jax.profiler import ProfileData

    raw = gzip.decompress((DATA / "tiny_v5e.xplane.pb.gz").read_bytes())
    tr = T.load(ProfileData.from_serialized_xspace(raw))
    ctx = types.SimpleNamespace(
        trace=tr, done=[types.SimpleNamespace(arrival_s=0.0)])
    assert [_reader(n)(ctx) for n in NEW] == [None, None, None]
    assert S.innermost(tr) == []
    idle = S.idle_by_span(tr)
    assert list(idle) == [S.OUTSIDE]
    assert idle[S.OUTSIDE] == pytest.approx(tr.window_s - T.busy_s(tr))


def test_tiny_cell_on_the_cpu_reads_the_spans(tmp_path):
    import jax

    root = tinycell.make_root(tmp_path / "root")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["per_layer"] += [{"name": n, "unit": "ms"} for n in NEW]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = spec.load_cell(tinycell.CELL, root)
    devices = jax.devices()[:1]
    keep = tmp_path / "kept"
    keep.mkdir()
    out = harness.run_cell(cell, 2 ** 33 + 5, 2.0, True, devices,
                           time.monotonic(), pallas_interpret=True,
                           keep_trace=str(keep))
    assert out["correct"] and out["failed"] == 0
    got = {n: out["metrics"][n]["value"] for n in NEW}
    assert all(v > 0 for v in got.values()), got
    tr = T.load(str(next(keep.glob("*.xplane.pb"))))
    assert len(S.spans(tr, S.ADMIT)) == out["attempted"]
    assert len(S.spans(tr, S.DECODE_SYNC)) == \
        out["stats"]["decode_chunks"]
    rows = idle_by_span.rows(tr)
    assert sum(r["idle_s"] for r in rows.values()) == pytest.approx(
        tr.window_s - T.busy_s(tr))
