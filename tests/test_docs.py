"""Doc drift: the docs are checked against the code, mechanically.

Four contracts:

  * every CLI flag of the train / serve / service / audit parsers is
    documented somewhere in README.md or docs/, and every flag-looking
    token the docs mention for THOSE tools actually exists (a removed
    flag cannot linger in prose);
  * every relative markdown link resolves to a real file, and every
    `#anchor` to a real heading in its target;
  * the engine-stats table in docs/serving.md is byte-identical to what
    `DecodeEngine.STATS_DOC` renders — the field list cannot rot;
  * every `python -m <module>` the docs and scripts/ci.sh name resolves,
    and every repo-relative path they name exists — a deleted module or
    script cannot linger in a command.
"""
import importlib.util
import os
import re

import pytest

from repro.launch.audit import build_audit_parser
from repro.launch.engine import DecodeEngine
from repro.launch.serve import build_serve_parser
from repro.launch.service import build_service_parser
from repro.launch.train import build_arg_parser

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DOC_FILES = ["README.md", "docs/architecture.md", "docs/serving.md",
             "docs/operations.md"]

# flag-looking tokens the docs legitimately mention that belong to OTHER
# CLIs (autotune sweep, dryrun, chip_smoke) or to env-var examples —
# anything else undocumented-in-a-parser is treated as stale
OTHER_CLI_FLAGS = {
    "--sweep", "--show", "--full",          # repro.kernels.autotune
    "--shape", "--audit",                   # repro.launch.dryrun
    "--chips",                              # chip_smoke.py
}

PARSERS = {
    "train": build_arg_parser,
    "serve": build_serve_parser,
    "service": build_service_parser,
    "audit": build_audit_parser,
}


def _read(rel):
    with open(os.path.join(ROOT, rel), encoding="utf-8") as fh:
        return fh.read()


def _parser_flags():
    flags = set()
    for build in PARSERS.values():
        for action in build()._actions:
            flags.update(o for o in action.option_strings
                         if o.startswith("--"))
    flags.discard("--help")
    return flags


def _doc_flags(text):
    # a flag mention: --word at a non-word boundary; strips the
    # XLA_FLAGS=--xla_... env examples below
    toks = set(re.findall(r"(?<![-\w])--[a-z][a-z0-9-]+", text))
    return {t for t in toks if not t.startswith("--xla")}


def test_every_cli_flag_is_documented():
    docs = "\n".join(_read(f) for f in DOC_FILES)
    documented = _doc_flags(docs)
    missing = sorted(_parser_flags() - documented)
    assert not missing, (
        f"CLI flags absent from README.md/docs/: {missing} — document "
        f"them (serve CLI table in docs/serving.md, train/service/audit "
        f"tables in docs/operations.md)")


def test_no_stale_documented_flags():
    known = _parser_flags() | OTHER_CLI_FLAGS
    stale = {}
    for f in DOC_FILES:
        bad = sorted(_doc_flags(_read(f)) - known)
        if bad:
            stale[f] = bad
    assert not stale, (
        f"docs mention flags no parser defines (removed or renamed?): "
        f"{stale}")


# --------------------------------------------------------------------------
# markdown links
# --------------------------------------------------------------------------

_LINK = re.compile(r"(?<!\!)\[[^\]]+\]\(([^)\s]+)\)")
_HEADING = re.compile(r"^#{1,6}\s+(.*?)\s*$", re.M)


def _anchor(heading):
    """GitHub heading -> anchor slug."""
    h = re.sub(r"`([^`]*)`", r"\1", heading).strip().lower()
    h = re.sub(r"[^\w\- ]", "", h)
    return h.replace(" ", "-")


def _anchors(text):
    return {_anchor(h) for h in _HEADING.findall(text)}


@pytest.mark.parametrize("doc", DOC_FILES)
def test_markdown_links_resolve(doc):
    text = _read(doc)
    base = os.path.dirname(os.path.join(ROOT, doc))
    problems = []
    for target in _LINK.findall(text):
        if target.startswith(("http://", "https://", "mailto:")):
            continue
        path, _, frag = target.partition("#")
        full = os.path.normpath(os.path.join(base, path)) if path \
            else os.path.join(ROOT, doc)
        if not os.path.exists(full):
            problems.append(f"{target}: file {path} not found")
            continue
        if frag:
            if not full.endswith(".md"):
                continue
            with open(full, encoding="utf-8") as fh:
                if frag not in _anchors(fh.read()):
                    problems.append(f"{target}: no heading for #{frag}")
    assert not problems, f"{doc}: broken links: {problems}"


# --------------------------------------------------------------------------
# module and path references
# --------------------------------------------------------------------------

_MODULE = re.compile(r"\bpython3? -m ([A-Za-z_][\w.]*)")
_PATH = re.compile(r"(?<![\w./-])(?:\./)?"
                   r"((?:scripts|benchmarks|bench|examples|tests|docs|src)"
                   r"/[\w./-]*)")


def _resolves(module):
    try:
        return importlib.util.find_spec(module) is not None
    except ModuleNotFoundError:  # a parent package is missing
        return False


@pytest.mark.parametrize("doc", DOC_FILES + ["scripts/ci.sh"])
def test_named_modules_and_paths_exist(doc, monkeypatch):
    text = _read(doc)
    monkeypatch.syspath_prepend(ROOT)
    monkeypatch.syspath_prepend(os.path.join(ROOT, "src"))
    missing = sorted({m for m in _MODULE.findall(text) if not _resolves(m)})
    paths = {p.rstrip(".") for p in _PATH.findall(text)}  # sentence ends
    missing += sorted(p for p in paths
                      if not os.path.exists(os.path.join(ROOT, p)))
    assert not missing, (
        f"{doc} names modules or paths that do not exist (deleted or "
        f"renamed?): {missing}")


# --------------------------------------------------------------------------
# engine-stats table
# --------------------------------------------------------------------------

def _render_stats_table():
    lines = ["| counter | meaning |", "|---|---|"]
    lines += [f"| `{k}` | {v} |" for k, v in DecodeEngine.STATS_DOC.items()]
    return "\n".join(lines)


def test_engine_stats_table_matches_stats_doc():
    text = _read("docs/serving.md")
    m = re.search(r"<!-- engine-stats:begin -->\n(.*?)\n"
                  r"<!-- engine-stats:end -->", text, re.S)
    assert m, "docs/serving.md lost its engine-stats block markers"
    assert m.group(1).strip() == _render_stats_table(), (
        "docs/serving.md engine-stats table is out of date — regenerate "
        "it from DecodeEngine.STATS_DOC (tests/test_docs.py"
        "::_render_stats_table)")


def test_stats_doc_covers_engine_stats():
    # the documented key set IS the runtime key set (STATS_DOC seeds
    # engine.stats, so a key added to one place only cannot hide)
    assert list(DecodeEngine.STATS_DOC), "STATS_DOC is empty?"
    src = _read("src/repro/launch/engine.py")
    assert "self.stats = {k: 0 for k in self.STATS_DOC}" in src, (
        "engine.stats no longer seeded from STATS_DOC — the docs table "
        "would silently diverge from the runtime counters")
