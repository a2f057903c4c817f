"""Export-surface satellites of the flight-recorder PR:

  - Prometheus text exposition (runtime/metrics.py): label values escape
    backslash/quote/newline per the 0.0.4 format, non-finite samples render
    as the +Inf/-Inf/NaN tokens (the old formatter raised OverflowError on
    int(inf)), and a parser round-trip recovers every (labels, value) pair.
  - latency-line emission parity (runtime/logemit.py): the vectorized
    grep_lines formatter, the stdout_line composition, and format_block
    (Python path, and the native C++ path when a toolchain is present) are
    BYTE-identical on a seeded 10k-line sample — including the
    `peer<id>/main` path prefix the reference awk scripts key on.
  - the `trace` CLI subcommand: a CPU mini-run emits a strict-JSON summary,
    a perfetto-loadable Chrome trace, and non-empty npz/csv sidecars.
"""

import io
import json
import math
import os
import re

import numpy as np
import pytest

from dst_libp2p_test_node_tpu.runtime.metrics import (
    Registry, _escape_label_value, _fmt_labels, _fmt_value,
)

# ------------------------------------------------------------- exposition


def test_fmt_value_nonfinite_tokens():
    assert _fmt_value(float("inf")) == "+Inf"
    assert _fmt_value(float("-inf")) == "-Inf"
    assert _fmt_value(float("nan")) == "NaN"
    assert _fmt_value(3.0) == "3.0"
    assert _fmt_value(0) == "0.0"
    assert _fmt_value(2.5) == "2.5"


def test_label_escaping():
    assert _escape_label_value('a"b') == 'a\\"b'
    assert _escape_label_value("a\\b") == "a\\\\b"
    assert _escape_label_value("a\nb") == "a\\nb"
    # backslash first: an embedded `\n` sequence must not double-escape
    assert _escape_label_value('\\"\n') == '\\\\\\"\\n'
    assert _fmt_labels({"k": 'v"1'}) == '{k="v\\"1"}'
    assert _fmt_labels({}) == ""


_SAMPLE = re.compile(r'^([a-zA-Z_:][a-zA-Z0-9_:]*)(?:\{(.*)\})? (\S+)$')
_LABEL = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')


def _unescape(v: str) -> str:
    # left-to-right scan: sequential str.replace passes mis-handle mixes
    # like `\\n` (escaped backslash followed by a literal n)
    return re.sub(r"\\(.)",
                  lambda m: {"n": "\n"}.get(m.group(1), m.group(1)), v)


def _parse_exposition(text: str):
    """prometheus text format 0.0.4 parser (samples only): name ->
    {frozenset(labels.items()): float}; +Inf/-Inf/NaN per the spec."""
    out: dict = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        m = _SAMPLE.match(line)
        assert m, f"unparseable exposition line: {line!r}"
        name, raw_labels, raw_v = m.groups()
        labels = {}
        if raw_labels:
            consumed = _LABEL.sub("", raw_labels).strip(", ")
            assert consumed == "", f"unparsed label residue {consumed!r}"
            for lm in _LABEL.finditer(raw_labels):
                labels[lm.group(1)] = _unescape(lm.group(2))
        v = {"+Inf": math.inf, "-Inf": -math.inf, "NaN": math.nan}.get(
            raw_v, None)
        if v is None:
            v = float(raw_v)
        out.setdefault(name, {})[frozenset(labels.items())] = v
    return out


def test_exposition_round_trip():
    reg = Registry()
    g = reg.gauge("rt_gauge", "round-trip gauge", ("path", "note"))
    cases = {
        ('shadow.data\\hosts', 'plain'): 1.5,
        ('he said "hi"', 'line1\nline2'): math.inf,
        ('trailing\\', 'q"\\n'): -math.inf,
        ('a', 'b'): math.nan,
        ('c', 'd'): 42.0,
    }
    for (p, n), v in cases.items():
        g.set(v, labels={"path": p, "note": n})
    reg.counter("rt_count", "unlabeled").inc(7)
    parsed = _parse_exposition(reg.render())
    assert parsed["rt_count"][frozenset()] == 7.0
    got = parsed["rt_gauge"]
    assert len(got) == len(cases)
    for (p, n), v in cases.items():
        key = frozenset({"path": p, "note": n}.items())
        assert key in got, (p, n)
        if math.isnan(v):
            assert math.isnan(got[key])
        else:
            assert got[key] == v


def test_histogram_le_labels_still_parse():
    reg = Registry()
    h = reg.histogram("rt_hist", "histogram", buckets=(10.0, 100.0))
    h.observe(5.0)
    h.observe(50.0)
    parsed = _parse_exposition(reg.render())
    b = parsed["rt_hist_bucket"]
    assert b[frozenset({("le", "10.0")})] == 1.0
    assert b[frozenset({("le", "+Inf")})] == 2.0
    assert parsed["rt_hist_sum"][frozenset()] == 55.0
    assert parsed["rt_hist_count"][frozenset()] == 2.0


# ------------------------------------------------------- logemit parity


def test_logemit_fast_paths_byte_identical():
    from dst_libp2p_test_node_tpu.runtime import native_logemit
    from dst_libp2p_test_node_tpu.runtime.logemit import (
        _STDOUT_TEMPLATE, grep_lines, stdout_line,
    )

    rng = np.random.default_rng(7)
    n = 10_000
    msg_id = 1234
    peers = rng.integers(0, 1_000_000, size=n).astype(np.int64)
    linenos = rng.integers(1, 500, size=n).astype(np.int64)
    delays = rng.integers(0, 250_000, size=n).astype(np.int64)

    # reference: per-line composition out of the two public primitives
    ref = "".join(
        f"{_STDOUT_TEMPLATE.format(pid=int(p))}:{int(ln)}:"
        f"{stdout_line(msg_id, int(d))}\n"
        for p, ln, d in zip(peers, linenos, delays))
    assert f"peer{int(peers[0])}/main" in ref  # the awk-split contract

    vec = "".join(s + "\n" for s in grep_lines(peers, msg_id, delays, linenos))
    assert vec == ref

    py_block = native_logemit.format_block(
        msg_id, peers, linenos, delays, force_python=True)
    assert py_block == ref

    if native_logemit.ensure_built():  # toolchain-gated native path
        native = native_logemit.format_block(msg_id, peers, linenos, delays)
        assert native == ref


@pytest.fixture
def logemit_sandbox(tmp_path, monkeypatch):
    """native_logemit pointed at a private copy of logemit.cpp, unloaded."""
    import shutil

    from dst_libp2p_test_node_tpu.runtime import native_logemit as nl

    if shutil.which("g++") is None:
        pytest.skip("no g++: the native emitter cannot be built here")
    shutil.copy(nl._SRC, tmp_path / "logemit.cpp")
    monkeypatch.setattr(nl, "_NATIVE_DIR", str(tmp_path))
    monkeypatch.setattr(nl, "_SRC", str(tmp_path / "logemit.cpp"))
    monkeypatch.setattr(nl, "_native", None)
    monkeypatch.setattr(nl, "_native_tried", False)
    return nl, tmp_path


def _block_args(n=5000):
    peers = np.arange(n, dtype=np.int64)
    return 99, peers, np.ones(n, dtype=np.int64), peers % 977


def _shadowlog_args(n=5000):
    from dst_libp2p_test_node_tpu.runtime import native_logemit as nl

    rng = np.random.default_rng(3)
    return "00:15:00 [shadow] 00:15:00 [INFO] pod-", rng.integers(
        0, 1 << 40, size=(n, nl.SHADOWLOG_FIELDS))


# the library's two formatters: name -> (function, its arguments, the
# counter of its native calls)
_FORMATTERS = {
    "format_block": ("format_block", _block_args, "native_blocks"),
    "format_shadowlog": ("format_shadowlog", _shadowlog_args,
                         "native_shadowlog_blocks"),
}


def test_shadowlog_fast_paths_byte_identical():
    """format_shadowlog's two formatters against a line composed here from
    the layout summary_shadowlog.awk parses (tests/test_bandwidth.py holds
    both to the reference loop on real counters)."""
    from dst_libp2p_test_node_tpu.runtime import native_logemit

    head, fields = _shadowlog_args(10_000)
    fields[::7] = 0
    fields[1::7] = np.iinfo(np.int64).max
    ref = "".join(
        f"{head}{i} n/a shadow heartbeat [node] heartbeat;{f[1]},{f[8]},0,0,0;"
        + ",".join(str(v) for v in (
            [0] * 24 + f[0:4] + [0, 0] + f[4:7] + [0, 0, 0]
            + f[7:11] + [0, 0] + f[11:14] + [0, 0, 0])) + "\n"
        for i, f in enumerate(fields.tolist()))
    assert native_logemit.format_shadowlog(
        head, fields, force_python=True) == ref
    if native_logemit.ensure_built():  # toolchain-gated native path
        before = native_logemit.native_shadowlog_blocks
        assert native_logemit.format_shadowlog(head, fields) == ref
        assert native_logemit.native_shadowlog_blocks == before + 1
    # under the threshold the Python formatter runs, whatever is built
    before = native_logemit.native_shadowlog_blocks
    few = native_logemit.NATIVE_MIN_LINES - 1
    assert native_logemit.format_shadowlog(head, fields[:few]) == "".join(
        ref.splitlines(keepends=True)[:few])
    assert native_logemit.native_shadowlog_blocks == before
    with pytest.raises(ValueError, match="fields must be"):
        native_logemit.format_shadowlog(head, fields[:, :13])


@pytest.mark.parametrize("formatter", list(_FORMATTERS))
def test_logemit_is_built_from_the_source_never_a_stale_binary(
        logemit_sandbox, monkeypatch, formatter):
    nl, d = logemit_sandbox
    name, args, counter = _FORMATTERS[formatter]
    fmt = getattr(nl, name)
    # a binary left over from some other version of the source, under the
    # old fixed name and older than logemit.cpp: never what runs
    (d / "liblogemit.so").write_bytes(b"not a library")
    os.utime(d / "liblogemit.so", (0, 0))
    assert nl.ensure_built()
    built = nl.lib_path()
    assert os.path.exists(built) and built != str(d / "liblogemit.so")
    before = getattr(nl, counter)
    native = fmt(*args())
    assert getattr(nl, counter) == before + 1
    assert native == fmt(*args(), force_python=True)
    # a changed source is a different library name: rebuilt, not reused
    with open(d / "logemit.cpp", "a") as f:
        f.write("\n// edited\n")
    monkeypatch.setattr(nl, "_native", None)
    monkeypatch.setattr(nl, "_native_tried", False)
    assert nl.lib_path() != built and not os.path.exists(nl.lib_path())
    assert nl.ensure_built() and os.path.exists(nl.lib_path())


@pytest.mark.parametrize("formatter", list(_FORMATTERS))
def test_logemit_build_failure_is_reported_once_and_bytes_stay_identical(
        logemit_sandbox, capfd, formatter):
    nl, d = logemit_sandbox
    name, args, counter = _FORMATTERS[formatter]
    fmt = getattr(nl, name)
    (d / "logemit.cpp").write_text("this is not C++\n")
    before = getattr(nl, counter)
    py = fmt(*args(), force_python=True)
    assert fmt(*args()) == py
    assert fmt(*args()) == py
    assert getattr(nl, counter) == before
    assert not nl.ensure_built()
    err = capfd.readouterr().err
    assert err.count("native log emitter unavailable") == 1


def test_latencies_writer_matches_parser():
    from dst_libp2p_test_node_tpu.runtime.logemit import LatenciesWriter
    from dst_libp2p_test_node_tpu.runtime.summarize import summarize

    w = LatenciesWriter()
    w.add_message(1, np.array([0, 1, 2]), np.array([100, 200, 300]))
    w.add_message(2, np.array([1, 2]), np.array([150, 250]))
    buf = io.StringIO()
    assert w.write_to(buf) == 5
    s = summarize(buf.getvalue().splitlines())
    assert s.total_messages == 2
    assert s.max_latency_ms == 300


# ------------------------------------------------------------ trace CLI


def test_trace_cli_smoke(tmp_path, capsys):
    from dst_libp2p_test_node_tpu.cli import main

    out_dir = str(tmp_path / "trace_out")
    rc = main(["trace", "-n", "32", "--connect-to", "4",
               "--heartbeats", "5", "--warmup-hb", "4", "--out", out_dir])
    assert rc == 0
    summary = json.loads(capsys.readouterr().out)
    assert summary["heartbeats"] == 5
    assert set(summary["channels"])  # non-empty channel list
    tj = os.path.join(out_dir, "trace.perfetto.json")
    doc = json.load(open(tj))
    assert any(e["ph"] == "X" for e in doc["traceEvents"])
    z = np.load(os.path.join(out_dir, "rounds.npz"))
    assert z["tel_mesh_coverage"].shape == (5,)
    csv_lines = open(os.path.join(out_dir, "rounds.csv")).read().splitlines()
    assert csv_lines[0].startswith("hb,")
    assert len(csv_lines) == 6  # header + one row per heartbeat
