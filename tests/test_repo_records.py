"""What the repository says about itself stays true.

The root holds no measurement records (the driver's ledger is
`PERF_LEDGER.jsonl`, the builders' account is PERF.md), the documents
name only files that exist, and the knob registry does not grow back.
"""

import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# BENCHMARK.json declares the benchmark, BASELINE.json is the reference's
# envelope (ROADMAP R5's source), WIRE_CONFORMANCE.json is raylint's corpus.
ROOT_JSON = {"BASELINE.json", "BENCHMARK.json", "WIRE_CONFORMANCE.json"}

# Raising this needs two callers at the parent commit, tests and examples
# not counted, that want different values (the simplicity guide's rule
# for an option); with one value in use, write a constant.
MAX_KNOBS = 73

_TREE_PREFIXES = ("scripts/", "ray_tpu/", "tests/", "benchmark/")


def test_root_holds_no_json_records():
    found = {os.path.basename(p) for p in glob.glob(os.path.join(REPO, "*.json"))}
    assert found == ROOT_JSON, sorted(found ^ ROOT_JSON)


def _named_paths(document):
    """Paths of this tree named in backticks: `scripts/x.py`,
    `python scripts/x.py --flag`, `tests/t.py::test_name`,
    `ray_tpu/ops/a.py:146`.  A `<placeholder>` is not a path."""
    with open(os.path.join(REPO, document), encoding="utf-8") as f:
        text = f.read()
    out = set()
    for span in re.findall(r"`([^`\n]+)`", text):
        for word in span.split():
            if word.startswith(_TREE_PREFIXES) and "<" not in word:
                word = word.split("::")[0]
                out.add(re.sub(r"(:\d+(-\d+)?)?[.,;)]*$", "", word))
    return sorted(out)


@pytest.mark.parametrize("document", ["README.md", "PERF.md"])
def test_documents_name_only_files_that_exist(document):
    paths = _named_paths(document)
    assert paths, f"{document} names no path of the tree: the reader is broken"
    missing = [p for p in paths if not glob.glob(os.path.join(REPO, p))]
    assert not missing, f"{document} names {missing}"


def test_knob_registry_does_not_grow():
    from ray_tpu.core import knobs

    registered = len(knobs.KNOBS)
    assert registered <= MAX_KNOBS, (
        f"{registered} knobs registered, {MAX_KNOBS} allowed: see the "
        "comment on MAX_KNOBS")
