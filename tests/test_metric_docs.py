"""Tier-1 guard: docs/OBSERVABILITY.md must name every metric the code
can emit under serving/, resilience/, store/, comm/ — via
tools/check_metric_docs.py, so the metric tables cannot drift; and, one
case a document, every flag and path a document puts between back-ticks
exists in the checkout."""
import importlib
import os

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
DOCUMENTS = importlib.import_module(
    "tools.check_metric_docs").documents(ROOT)


@pytest.fixture(scope="module")
def checker():
    return importlib.import_module("tools.check_metric_docs")


def test_all_emitted_metric_names_documented(checker, capsys):
    rc = checker.main(["--root", ROOT])
    err = capsys.readouterr().err
    assert rc == 0, f"undocumented metric names:\n{err}"


def test_scan_finds_known_call_sites(checker):
    """The scanner must actually see direct literals, helper
    indirections (_count/_observe_ms), and f-string templates — a
    regex regression that finds nothing would make the check vacuous."""
    emitted = checker.emitted_names(ROOT)
    assert "serving/ttft_ms" in emitted                     # direct literal
    assert "store/hits" in emitted                          # _count helper
    assert "resilience/offload_uploads" in emitted          # _count helper
    assert any("{" in n for n in emitted)                   # f-string kept
    assert len(emitted) > 50


def test_undocumented_name_is_flagged(checker):
    """A fresh metric name with no doc entry must fail the check."""
    with open(os.path.join(ROOT, "docs", "OBSERVABILITY.md")) as f:
        names, wild = checker.documented_forms(f.read())
    assert not checker.is_documented(
        "serving/definitely_not_documented_xyz", names, wild)
    # and the real, documented forms pass through all three paths:
    assert checker.is_documented("serving/ttft_ms", names, wild)
    assert checker.is_documented(                           # <i> placeholder
        'serving/replica/{replica.replica_id}/queue_depth', names, wild)
    assert checker.is_documented(                           # wildcard family
        "serving/autoscaler_{action}", names, wild)


def test_bare_group_wildcard_is_not_vacuous(checker):
    """The `serving/*` namespace header must not count as documenting
    arbitrary serving names."""
    names, wild = checker.documented_forms(
        "groups: `serving/*`, `store/*`\n")
    assert not checker.is_documented("serving/brand_new_name", names, wild)


@pytest.mark.parametrize("doc", DOCUMENTS)
def test_document_names_only_flags_and_files_that_exist(checker, doc):
    """A document that cites a deleted file or a removed option fails
    here, by name, until the sentence is rewritten."""
    assert checker.stale_references(ROOT, doc) == []


def test_stale_reference_scan_sees_flags_paths_and_patterns(
        checker, tmp_path):
    assert len(DOCUMENTS) == 11 and "docs/SERVING.md" in DOCUMENTS
    (tmp_path / "flexflow_tpu").mkdir()
    (tmp_path / "flexflow_tpu" / "config.py").write_text(
        'p.add_argument("--kept", dest="kept")\n')
    (tmp_path / "scripts").mkdir()
    (tmp_path / "scripts" / "probe.py").write_text(
        "ap.add_argument('--mine')\n")
    # a scratch checkout outside the TREES does not stand in for a
    # file the tree lost
    (tmp_path / ".chipcheck" / "parent").mkdir(parents=True)
    (tmp_path / ".chipcheck" / "parent" / "gone.py").write_text("")
    (tmp_path / "doc.md").write_text(
        "`--kept` and `python scripts/probe.py --mine 3` are fine, as\n"
        "are `scripts/{probe,probe}.py`, `scripts/<name>.py`, `probe.py`\n"
        "and a run's `trace.json`; prose --dashes are not flags.\n"
        "```bash\nchiprun --chips 1 -- python3 scripts/probe.py "
        "--gone\n```\n"
        "`--removed-flag`, `gone.py` and `scripts/gone_*.py` are not.\n")
    assert checker.stale_references(str(tmp_path), "doc.md") == [
        "`--gone`: no parser of FFConfig or of a script this document "
        "names defines it",
        "`--removed-flag`: no parser of FFConfig or of a script this "
        "document names defines it",
        "`gone.py`: no such file in the checkout",
        "`scripts/gone_*.py`: no such file in the checkout",
    ]
