"""Doc staleness tripwire (VERDICT r3 ask #9).

Committed-artifact numbers quoted in README.md / PARITY.md must match the
artifacts they quote. Doc drift survived two judging rounds because nothing
executable pinned the prose to the data; this test greps the docs for the
quoted numbers and fails on mismatch, so a model/benchmark change cannot
ship without its doc lines.
"""

import ast
import functools
import glob
import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _artifact():
    rows = {}
    with open(os.path.join(ROOT, "BENCH_CONFIGS.json")) as f:
        for line in f:
            line = line.strip()
            if line:
                d = json.loads(line)
                rows[d["config"]] = d
    return rows


def _read(name):
    with open(os.path.join(ROOT, name)) as f:
        return f.read()


def _fmt_k(v: float) -> str:
    """peers*rounds/s as the README table prints it (thousands, 1 dp)."""
    return f"{v / 1e3:.1f}k"


def test_readme_config_table_matches_artifact():
    rows = _artifact()
    readme = _read("README.md")
    # the five ladder rows: | N | <desc> | wall | rounds | cov | p50 / p99 |
    pat = re.compile(
        r"^\|\s*(\d)\s*\|[^|]+\|\s*([\d.]+)\s*\|\s*([\d.]+k)\s*\|"
        r"\s*([\d.]+)\*?\s*\|\s*(\d+)\s*/\s*(\d+)\s*\|",
        re.M,
    )
    found = {int(m[0]): m for m in pat.findall(readme)}
    assert set(found) == set(rows), (
        f"README config table rows {sorted(found)} != artifact {sorted(rows)}"
    )
    for c, art in rows.items():
        cfg, wall, rps, cov, p50, p99 = found[c]
        assert float(wall) == pytest.approx(art["wall_s"], abs=0.051), \
            f"README config {c} wall {wall} != artifact {art['wall_s']}"
        assert rps == _fmt_k(art["peer_rounds_per_sec"]), \
            f"README config {c} rate {rps} != {_fmt_k(art['peer_rounds_per_sec'])}"
        assert float(cov) == pytest.approx(art["coverage"], abs=0.0051), \
            f"README config {c} coverage {cov} != artifact {art['coverage']}"
        assert int(p50) == round(art["p50_ms"]), \
            f"README config {c} p50 {p50} != artifact {art['p50_ms']}"
        assert int(p99) == round(art["p99_ms"]), \
            f"README config {c} p99 {p99} != artifact {art['p99_ms']}"


def test_parity_flagship_number_matches_artifact():
    rows = _artifact()
    parity = _read("PARITY.md")
    # PARITY quotes the flagship number via the canonical phrase
    # "config-5 wall <num> s" (this exact figure was stale two rounds
    # running); any other phrasing is itself a failure — an unanchored
    # number is how the drift survived
    quoted = re.findall(r"config-5 wall ([\d.]+)\s*s\b", parity)
    assert quoted, (
        "PARITY.md must quote the flagship number with the canonical "
        "phrase 'config-5 wall <num> s' so this tripwire can pin it"
    )
    for q in quoted:
        assert float(q) == pytest.approx(rows[5]["wall_s"], abs=0.051), (
            f"PARITY.md quotes config-5 wall {q} s; committed artifact says "
            f"{rows[5]['wall_s']} s — update the doc"
        )


def test_validity_doc_matches_anchor_artifact():
    # docs/VALIDITY.md quotes the Ethereum-anchor run's numbers; they must
    # be the committed docs/VALIDITY_ANCHOR.json values (same drift class
    # as the PARITY flagship number)
    with open(os.path.join(ROOT, "docs", "VALIDITY_ANCHOR.json")) as f:
        anchor = json.load(f)["ours"]
    doc = _read(os.path.join("docs", "VALIDITY.md"))
    m = re.search(r"\| p50 dissemination \| \*\*(\d+) ms\*\* \|", doc)
    assert m, "VALIDITY.md must quote '| p50 dissemination | **<n> ms** |'"
    assert int(m[1]) == round(anchor["p50_ms"]), (m[1], anchor["p50_ms"])
    m = re.search(r"\| max \| (\d+) ms \|", doc)
    assert m and int(m[1]) == round(anchor["max_ms"]), (
        "VALIDITY.md max must quote the artifact", anchor["max_ms"])


_RATE = re.compile(
    r"\d[\d.,]*\s*[kM]?\s*(?:simulated\s+)?peers?\s*[×x*·-]\s*"
    r"(?:heartbeat-)?rounds", re.I)
# `runsh-100k.headline`, `attack-2k.sybil`: <configuration>.<traffic mix>
_CELL = re.compile(r"`([a-z]+-\d+[km]?(?:-[a-z0-9]+)*\.[a-z]+)`")
_FILE_SUFFIXES = ("json", "jsonl", "py", "md", "yaml", "npz", "csv", "gml")


def _outside_ladder(readme: str) -> str:
    """README less "The five scaling configs": that section's table is a
    copy of BENCH_CONFIGS.json, rate column included, held to it by
    test_readme_config_table_matches_artifact (pre-chip rows, says its note)."""
    head, _, rest = readme.partition("## The five scaling configs")
    return head + rest[rest.index("\n## "):]


def test_metric_of_record_quote_matches_artifact():
    # The speed of record is the driver's ledger, in seconds an experiment
    # a cell. README and PARITY therefore quote NO peer-rounds/s figure (the
    # pre-chip "13.8M" survived in both for thirty PRs because this test
    # used to require it), and every cell they name is one BENCHMARK.json
    # declares.
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cells = {w["name"] for w in json.load(f)["workloads"]}
    for name in ("README.md", "PARITY.md"):
        doc = _read(name)
        scanned = _outside_ladder(doc) if name == "README.md" else doc
        m = _RATE.search(scanned)
        assert not m, (
            f"{name} quotes a peer-rounds/s figure ({m[0]!r}): speeds are "
            "ledger lines with their origin, or 'not measured'")
        named = {c for c in _CELL.findall(doc)
                 if c.rsplit(".", 1)[1] not in _FILE_SUFFIXES}
        assert named <= cells, (
            f"{name} names cells BENCHMARK.json does not declare: "
            f"{sorted(named - cells)}")
    assert _CELL.findall(_read("README.md")), \
        "README must state its speed by cell (a ledger line)"


def test_validity_doc_matches_second_anchor_artifact():
    # the attestation-scale anchor's quoted numbers (docs/VALIDITY.md §2)
    # must be the committed docs/VALIDITY_ANCHOR2.json values
    with open(os.path.join(ROOT, "docs", "VALIDITY_ANCHOR2.json")) as f:
        anchor = json.load(f)["ours"]
    doc = _read(os.path.join("docs", "VALIDITY.md"))
    p50s = re.findall(r"\| p50 dissemination \| \*\*(\d+) ms\*\* \|", doc)
    assert len(p50s) == 2, "VALIDITY.md must quote both anchors' p50"
    assert int(p50s[1]) == round(anchor["p50_ms"]), (p50s[1], anchor["p50_ms"])
    m = re.search(r"\| p99 \| (\d+) ms \|", doc)
    assert m and int(m[1]) == round(anchor["p99_ms"]), (
        "VALIDITY.md must quote the attestation anchor p99", anchor["p99_ms"])


def test_validity_muxer_sensitivity_quotes_match_artifact():
    # the muxer-axis bound quoted in docs/VALIDITY.md §3 must be the
    # committed sensitivity table (event_loop_calibration.json)
    with open(os.path.join(ROOT, "docs", "event_loop_calibration.json")) as f:
        span = json.load(f)["muxer_sensitivity"]["span"]
    doc = _read(os.path.join("docs", "VALIDITY.md"))
    m = re.search(r"p50\s*moves ([\d.]+)%", doc)
    assert m and float(m[1]) == pytest.approx(span["p50_span_pct"],
                                              abs=0.006), (
        m and m[1], span["p50_span_pct"])
    m = re.search(r"moves it ([\d.]+)%", doc)
    assert m and float(m[1]) == pytest.approx(span["p50_bound_shift_pct"],
                                              abs=0.006), (
        m and m[1], span["p50_bound_shift_pct"])


def test_readme_delivery_mode_quotes_match_bench_artifact():
    # README's delivery-modes section used to quote the exact/bounded
    # publish walls of a CPU bench artifact. No cell runs the bounded mode,
    # so until one does the section must say so, not quote a cost.
    readme = _read("README.md")
    section = readme.partition("## Delivery-fidelity modes")[2]
    section = section[:section.index("\n## ")]
    assert "**bounded**" in section
    assert "not measured on a chip" in section, (
        "README's delivery-modes section must state the bounded mode's "
        "cost as 'not measured on a chip'")
    assert not re.search(r"[\d.]+ s/publish", section)


def test_readme_loss_tail_matches_artifact():
    # README's loss-model section quotes the tcp-mode deep-backoff tail;
    # pin it to docs/LOSS_MODES.json like every other quoted artifact
    with open(os.path.join(ROOT, "docs", "LOSS_MODES.json")) as f:
        runs = json.load(f)["runs"]
    tcp_hi = next(r for r in runs
                  if r["loss_mode"] == "tcp" and r["loss"] >= 0.1)
    readme = _read("README.md")
    m = re.search(r"RTO tail \(max ([\d.]+) s", readme)
    assert m, "README must quote the tcp-mode tail as 'RTO tail (max <n> s'"
    assert float(m[1]) == pytest.approx(tcp_hi["max_ms"] / 1e3, abs=0.051), (
        m[1], tcp_hi["max_ms"])


def test_parity_test_file_count_matches_tree():
    parity = _read("PARITY.md")
    m = re.search(r"(\d+)\s+test files", parity)
    assert m, "PARITY.md should state the test-file count"
    actual = len(glob.glob(os.path.join(ROOT, "tests", "test_*.py")))
    assert int(m[1]) == actual, (
        f"PARITY.md claims {m[1]} test files; tests/ has {actual}"
    )


def test_readme_delivery_mode_labels_match_bench_configs():
    # Which delivery mode each ladder row ran is part of the row's meaning
    # (bounded numbers carry an error bar, exact ones do not), and the
    # README's prose labels drifted from the artifact once already: the
    # committed config-4 row stayed bounded for two rounds after exact
    # became its default. bench_configs.py now records delivery_mode in
    # every gossip-bearing row; the README must label each such config
    # with the canonical phrase 'config N runs the <mode> delivery mode'
    # and the label must match the artifact.
    rows = _artifact()
    tagged = {c: r["delivery_mode"] for c, r in rows.items()
              if "delivery_mode" in r}
    assert tagged, "no BENCH_CONFIGS.json row records delivery_mode"
    readme = _read("README.md")
    labeled = {int(c): mode for c, mode in re.findall(
        r"[Cc]onfig\s+(\d)\s+runs\s+the\s+(exact|bounded)\s+delivery\s+mode",
        readme)}
    for c, mode in sorted(tagged.items()):
        assert c in labeled, (
            f"README must label config {c} with the canonical phrase "
            f"'config {c} runs the <mode> delivery mode'")
        assert labeled[c] == mode, (
            f"README labels config {c} as {labeled[c]}; committed "
            f"BENCH_CONFIGS.json row says {mode} — update the doc")


# ------------------------------------------------- names that must exist --

PKG = os.path.join(ROOT, "dst_libp2p_test_node_tpu")
_PKG_DIRS = ("ops", "runtime", "native", "parallel", "config", "analysis")
_ROOT_DIRS = ("dst_libp2p_test_node_tpu", "tests", "scripts", "docs",
              "benchmark", "deploy")
# the reference's own files, which the documents cite by line
_REFERENCE_FILES = {"topogen.py", "traffic_sync.py"}
_TOKEN = re.compile(r"`([^`\n]+)`")
_PATHLIKE = re.compile(r"^(?:[\w.-]+/)*[\w.-]+$")
_RECORD = re.compile(r"^[A-Z][A-Z0-9_]*(?:_r\d+)?\.jsonl?$")


@functools.lru_cache(maxsize=None)
def _every_py() -> frozenset[str]:
    """Names of the Python files of the checkout's own directories (not of
    whatever else lies beside them: scratch copies, build outputs)."""
    names = {os.path.basename(f) for f in glob.glob(os.path.join(ROOT, "*.py"))}
    for top in _ROOT_DIRS:
        for _, _, files in os.walk(os.path.join(ROOT, top)):
            names.update(f for f in files if f.endswith(".py"))
    return frozenset(names)


def _exists(base: str, path: str) -> bool:
    """`path` under `base`: a file, a directory, or `<module>.<attribute>`
    of a module that exists and spells the attribute."""
    full = os.path.join(base, path)
    if os.path.exists(full):
        return True
    module, dot, attr = full.rpartition(".")
    if dot and os.path.isfile(module + ".py"):
        with open(module + ".py") as f:
            return re.search(rf"\b{re.escape(attr)}\b", f.read()) is not None
    return False


def _missing_paths(doc: str) -> list[str]:
    every_py = _every_py()
    missing = []
    for tok in _TOKEN.findall(_read(doc)):
        if any(c in tok for c in "<*{…$"):
            continue        # a pattern or a placeholder, not one path
        path = re.split(r"\s|::|:", tok.strip())[0].rstrip(".,;)")
        if not _PATHLIKE.match(path):
            continue
        head = path.split("/")[0]
        if "/" not in path:
            if path.endswith(".py"):
                ok = path in every_py or path in _REFERENCE_FILES
            elif _RECORD.match(path):
                ok = (os.path.exists(os.path.join(ROOT, path))
                      or os.path.exists(os.path.join(ROOT, "docs", path)))
            else:
                continue    # an artifact a run writes, a word
        elif head in _PKG_DIRS:
            ok = _exists(PKG, path)
        elif head in _ROOT_DIRS:
            ok = _exists(ROOT, path)
        else:
            continue
        if not ok:
            missing.append(tok)
    return sorted(set(missing))


@pytest.mark.parametrize("doc", [
    "README.md", "PARITY.md", os.path.join("docs", "ARCHITECTURE.md"),
    "PERF.md", os.path.join("docs", "VALIDITY.md")])
def test_every_repository_path_a_document_names_exists(doc):
    # a deleted module, script or record that a document still points at
    # (`bench.py` and its BENCH_r*.json outlived their use by thirty PRs)
    assert _missing_paths(doc) == []


def _dispatched_subcommands() -> set[str]:
    with open(os.path.join(PKG, "cli.py")) as f:
        tree = ast.parse(f.read())
    main = next(n for n in tree.body
                if isinstance(n, ast.FunctionDef) and n.name == "main")
    return {
        n.comparators[0].value for n in ast.walk(main)
        if isinstance(n, ast.Compare) and isinstance(n.left, ast.Name)
        and n.left.id == "cmd" and isinstance(n.ops[0], ast.Eq)
        and isinstance(n.comparators[0], ast.Constant)}


def test_subcommands_in_readme_and_cli_docstring_are_dispatched(capsys):
    from dst_libp2p_test_node_tpu import cli

    dispatched = _dispatched_subcommands()
    assert {"run", "topogen", "lint"} <= dispatched
    listed = set(re.findall(r"^  (\w+)\s+— ", cli.__doc__, re.M))
    assert listed == dispatched, (
        "cli.py's docstring lists", sorted(listed ^ dispatched),
        "differently from what main dispatches")
    invoked = set()
    for block in re.findall(r"```(?:sh|bash)?\n(.*?)```", _read("README.md"),
                            re.S):
        invoked.update(re.findall(
            r"python3? -m dst_libp2p_test_node_tpu(?:\.cli)? ([a-z]+)", block))
    assert invoked and invoked <= dispatched, sorted(invoked - dispatched)
    # a deleted subcommand is the unknown-command exit, not a stub
    assert cli.main(["microbench"]) == 2
    assert "unknown command: microbench" in capsys.readouterr().err
