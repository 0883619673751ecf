"""The documents name files that exist.

One case a document: every backticked token that looks like a path of
this repository (it ends in a source or data suffix, with any
``::name`` or ``:line`` cut off) must be a file or directory of the
checkout, read from the root or from the package (the documents write
``service/runtime.py`` for ``multidisttorch_tpu/service/runtime.py``).
A deleted script, module or record that a sentence still points at
fails here."""

import itertools
import pathlib
import re

import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent
DOCS = ["README.md"] + sorted(
    str(p.relative_to(ROOT)) for p in (ROOT / "docs").glob("*.md")
)
SUFFIXES = (".py", ".cpp", ".md", ".json", ".jsonl", ".yml", ".toml")

# Names a run writes into its own output directory (the sentence around
# each says which command writes it), not files of the checkout.
RUN_OUTPUTS = {
    "events.jsonl", "trace.json", "summary.json", "metrics.json",
    "fleet_events.jsonl", "fleet_trace.json", "fleet_summary.json",
    "worlds.jsonl", "queue.jsonl", "service_books.json",
    "incidents.jsonl", "report.json", "perfetto.json",
    "affected_traces.json", "chunks/refs.json", "fabric/topology.jsonl",
    "fabric/shard-k.steal.jsonl", "ctlprof_ledger.jsonl",
}
# The reference implementation's files (ORNL/MultiDistTorch, SURVEY.md),
# cited by line beside what replaces them here.
REFERENCE_FILES = {"vae-hpo.py", "utils.py", "example-subgroup.py"}


def _braces(token):
    """`a/{b,c}.py` -> `a/b.py`, `a/c.py`."""
    parts = re.split(r"\{([^{}]*,[^{}]*)\}", token)
    choices = [p.split(",") if i % 2 else [p] for i, p in enumerate(parts)]
    return ["".join(c) for c in itertools.product(*choices)]


def _paths(text):
    for token in re.findall(r"`([^`\n]+)`", text):
        token = re.split(r"::|:\d", token.strip())[0]
        if " " in token or not token.endswith(SUFFIXES):
            continue
        for path in _braces(token):
            # <id>, {run_dir}, *.json: a template, not a name
            if not re.search(r"[<>{}*$]", path):
                yield path


@pytest.mark.parametrize("doc", DOCS)
def test_paths_named_in_a_document_exist(doc):
    missing = sorted(
        p
        for p in set(_paths((ROOT / doc).read_text()))
        if p not in RUN_OUTPUTS | REFERENCE_FILES
        and not (ROOT / p).exists()
        and not (ROOT / "multidisttorch_tpu" / p).exists()
    )
    assert not missing, f"{doc} names what the checkout lacks: {missing}"
