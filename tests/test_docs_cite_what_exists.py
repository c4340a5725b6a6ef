"""The documents that describe the system as it is cite what exists.

One case per document and property. The documents: `README.md`,
`PARITY.md`, `BASELINE.md`, every `docs/*.md`, every `docs/evidence/*.md`
and the verify skill's notes. The histories (`PERF.md`, `ROADMAP.md`,
`CHANGES.md`) are not cases: they rightly name files that went.
`benchmark/README.md` is not a case either: only a `benchmark` PR may
edit it.

Two properties:

- every back-quoted word that is a path of this repo (it starts with one
  of the repo's top-level directories, or is a bare `*.py` / `*.md` file
  name) exists, after `:line`, `:a-b` and `::name` suffixes are taken
  off; and the document names none of the second benchmark's parts,
  which went in PR 30 (`PERF.md` and the ledger are the one account of
  speed);
- every `--flag` in a back-quoted span or a code block is accepted by
  some `argparse` parser in the repo's Python files (read from the
  source, nothing is imported), or is on the short list of flags that
  belong to outside tools.
"""

import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# What only the working directory of a builder holds, never a checkout.
_NOT_THE_REPO = {
    ".git", ".proof", ".scratch_chip", ".bench_trace", ".jax_cache",
    ".pytest_cache", ".hypothesis", "__pycache__", "chiprun_out",
}


def _documents():
    docs = ["README.md", "PARITY.md", "BASELINE.md"]
    for sub in ("docs", os.path.join("docs", "evidence")):
        docs += sorted(
            os.path.join(sub, f)
            for f in os.listdir(os.path.join(REPO, sub))
            if f.endswith(".md")
        )
    docs.append(os.path.join(".claude", "skills", "verify", "SKILL.md"))
    return docs


DOCUMENTS = _documents()

# The directories a cited path may start with. `traces/` is where runs
# write captures: nothing is committed there, so a document may name the
# directory but no file in it.
PATH_PREFIXES = (
    "torched_impala_tpu/", "tests/", "tools/", "benchmark/", "docs/",
    "traces/", "examples/", ".claude/",
)
# Parts of the benchmark that PR 30 removed; no document but a history
# names them.
GONE = ("bench.py", "perfgate", "BENCH_HISTORY")

# Flags of tools this repo does not own: argparse's own, pytest-xdist's
# (the tier-1 command), and XLA's (set through XLA_FLAGS).
OUTSIDE_FLAGS = {
    "--help", "--dist", "--xla_force_host_platform_device_count",
}

_SPAN = re.compile(r"`([^`\n]+)`")
_FENCE = re.compile(r"```.*?```", re.S)
_SUFFIX = re.compile(r"(::[\w\[\].,-]+|:\d+(-\d+)?(,\d+(-\d+)?)*)+$")
_BARE_FILE = re.compile(r"[\w.-]+\.(py|md)")
_FLAG = re.compile(r"(?<![\w-])--[a-zA-Z][\w-]*")
_ADD_ARGUMENT = re.compile(r"add_argument\(\s*((?:\"[^\"]+\"\s*,\s*)*)")


@pytest.fixture(scope="module")
def repo_files():
    """(directory, file name) of every file of the checkout, read once."""
    found = []
    for d, dirs, files in os.walk(REPO):
        dirs[:] = [x for x in dirs if x not in _NOT_THE_REPO]
        found += [(d, f) for f in files]
    return found


@pytest.fixture(scope="module")
def file_names(repo_files):
    return {f for _, f in repo_files}


@pytest.fixture(scope="module")
def parser_flags(repo_files):
    flags = set()
    for d, f in repo_files:
        if not f.endswith(".py"):
            continue
        with open(os.path.join(d, f)) as fh:
            src = fh.read()
        for m in _ADD_ARGUMENT.finditer(src):
            flags.update(re.findall(r"\"(--[\w-]+)\"", m.group(1)))
    return flags


def _read(doc):
    with open(os.path.join(REPO, doc)) as f:
        return f.read()


def _cited_words(text):
    for m in _SPAN.finditer(text):
        for word in m.group(1).split():
            yield word.strip("()[],;'\"").rstrip(".,:")


@pytest.mark.parametrize("doc", DOCUMENTS)
def test_cited_paths_exist_and_nothing_gone_is_named(doc, file_names):
    text = _read(doc)
    missing = set()
    for word in _cited_words(text):
        path = _SUFFIX.sub("", word)
        if any(c in path for c in "<*{$"):
            continue  # a pattern, not one path
        if path.startswith(PATH_PREFIXES):
            if path.rstrip("/") == "traces":
                continue  # made by the first run that writes a capture
            if not os.path.exists(os.path.join(REPO, path)):
                missing.add(word)
        elif _BARE_FILE.fullmatch(path) and path not in file_names:
            missing.add(word)
    assert not missing, f"{doc} cites what is not there: {sorted(missing)}"
    named = [name for name in GONE if name in text]
    assert not named, (
        f"{doc} names {named}: removed in PR 30; speed is PERF.md's and "
        "the ledger's to state"
    )


@pytest.mark.parametrize("doc", DOCUMENTS)
def test_cited_flags_are_accepted_by_a_parser(doc, parser_flags):
    text = _read(doc)
    cited = set()
    for block in _FENCE.findall(text):
        cited.update(_FLAG.findall(block))
    for m in _SPAN.finditer(_FENCE.sub("", text)):
        cited.update(_FLAG.findall(m.group(1)))
    stale = sorted(cited - parser_flags - OUTSIDE_FLAGS)
    assert not stale, (
        f"{doc} names flags no parser in the repo accepts: {stale} "
        "(correct the document, not the parser)"
    )
