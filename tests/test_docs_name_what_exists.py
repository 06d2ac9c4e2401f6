"""The documents name files, scripts and subcommands that exist.

A document that tells its reader to run a script, open a file or call a
subcommand that the tree no longer has is how a deleted measurement system
stays alive on paper. Three rules over the code a document quotes (inline
``code`` spans and fenced blocks), each decided by the token's own form:

(i)   a word whose first path component is one of the repository's top-level
      directories names something in the tree: a ``:line`` suffix is
      stripped, a name without an extension may be a module or a directory,
      and a pattern (``*``, ``<placeholder>``, ``{a,b}``, ``…``) has to
      match at least one file;
(ii)  ``python X.py`` / ``python3 X.py`` names a script that exists (from the
      root of the checkout), ``python -m tony_tpu.…`` a module that exists;
(iii) ``tony-tpu <subcommand>`` is a subcommand the CLI's parser has.

Bare names of job artifacts (``perf.json``), paths under a placeholder
(``<job_dir>/incident.json``) and the reference's files start with no
directory of this repository, so no rule reads them."""

import glob
import itertools
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOP_DIRS = ("tony_tpu", "tests", "docs", "examples", "benchmarks", ".github",
            ".claude")
DOCUMENTS = ("README.md", "docs/DESIGN.md", "docs/development.md",
             "docs/operations.md", "docs/parallelism.md",
             "docs/migrating-from-tony.md", ".claude/skills/verify/SKILL.md")

FENCE = re.compile(r"^[ \t]*```.*?$(.*?)^[ \t]*```\s*$", re.S | re.M)
INLINE = re.compile(r"`([^`\n]+(?:\n[^`\n]+)?)`")
LINE_SUFFIX = re.compile(r":\d[\d,–-]*$")
PLACEHOLDER = re.compile(r"<[^<>]*>|\.\.\.|…")
BRACES = re.compile(r"\{([^{}]*,[^{}]*)\}")
SCRIPT = re.compile(r"\bpython3?\s+((?!-)\S+\.py)\b")
MODULE = re.compile(r"\bpython3?\s+-m\s+(tony_tpu(?:\.\w+)*)")
SUBCOMMAND = re.compile(r"(?<![\w/.-])tony-tpu\s+([a-z][a-z-]*)")


def quoted_code(text):
    """Every piece of code the document quotes: fenced blocks, then the
    inline spans of what is left."""
    blocks = [m.group(1) for m in FENCE.finditer(text)]
    return blocks + [m.group(1) for m in INLINE.finditer(FENCE.sub("", text))]


def expand_braces(pattern):
    m = BRACES.search(pattern)
    if not m:
        return [pattern]
    return list(itertools.chain.from_iterable(
        expand_braces(pattern[:m.start()] + part + pattern[m.end():])
        for part in m.group(1).split(",")))


def in_tree(word):
    """Does ``word``, a path from the root of the checkout, name something
    there?"""
    path = LINE_SUFFIX.sub("", word).rstrip("/")
    return all(glob.glob(os.path.join(REPO, candidate))
               or glob.glob(os.path.join(REPO, candidate + ".py"))
               for candidate in expand_braces(PLACEHOLDER.sub("*", path)))


def tree_words(code):
    for word in code.split():
        word = word.strip("\"'()[],;").rstrip(".:")
        if "/" in word and word.split("/", 1)[0] in TOP_DIRS:
            yield word


def module_exists(dotted):
    base = os.path.join(REPO, *dotted.split("."))
    return os.path.isfile(base + ".py") \
        or os.path.isfile(os.path.join(base, "__main__.py"))


@pytest.fixture(scope="module")
def subcommands():
    from tony_tpu.cli.main import build_parser

    choices = set()
    for action in build_parser()._subparsers._group_actions:
        choices.update(action.choices)
    return choices


@pytest.mark.parametrize("document", DOCUMENTS)
def test_document_names_what_exists(document, subcommands):
    with open(os.path.join(REPO, document), encoding="utf-8") as f:
        code = quoted_code(f.read())
    missing = []
    for piece in code:
        missing += [f"path {w}" for w in tree_words(piece)
                    if not in_tree(w)]
        missing += [f"script {s}" for s in SCRIPT.findall(piece)
                    if not PLACEHOLDER.search(s)
                    and not os.path.isfile(os.path.join(REPO, s))]
        missing += [f"module {m}" for m in MODULE.findall(piece)
                    if not module_exists(m)]
        missing += [f"subcommand tony-tpu {c}"
                    for c in SUBCOMMAND.findall(piece)
                    if c not in subcommands]
    assert not missing, f"{document} names what the tree does not have: " \
        + "; ".join(sorted(set(missing)))
