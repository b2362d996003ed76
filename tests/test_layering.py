"""The repository's shape: the product packages import nothing above them,
the documents name only files that exist, and the serving lanes' one replica
model does the arithmetic its docstring says.
"""

from __future__ import annotations

import ast
import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# What the image ships (infra/Dockerfile copies exactly these two).
PRODUCT_PACKAGES = ("tpu_engine", "backend")
# What it does not: a product module that imports one of these works in a
# checkout and raises ModuleNotFoundError where the product runs.
ABOVE_THE_PRODUCT = {"benchmarks", "tools", "bench"}

DOCUMENTS = ("README.md", "docs/*.md", ".claude/skills/verify/SKILL.md")
_SCRIPT_PATH = re.compile(r"\b(?:benchmarks|tools)/[\w/]+\.py\b|\bbench\.py\b")


def _upward_imports(path: str) -> list[str]:
    with open(path) as f:
        tree = ast.parse(f.read(), filename=path)
    found = []
    for node in ast.walk(tree):  # every depth: a function's import counts
        if isinstance(node, ast.Import):
            modules = [alias.name for alias in node.names]
        elif isinstance(node, ast.ImportFrom) and node.level == 0:
            modules = [node.module or ""]
        else:
            continue
        found += [
            f"{os.path.relpath(path, REPO)}:{node.lineno}: {m}"
            for m in modules if m.split(".")[0] in ABOVE_THE_PRODUCT
        ]
    return found


def test_product_packages_import_nothing_above_them():
    files = [
        p for pkg in PRODUCT_PACKAGES
        for p in glob.glob(os.path.join(REPO, pkg, "**", "*.py"), recursive=True)
    ]
    assert len(files) > 50, "the product packages were not found"
    upward = [hit for p in sorted(files) for hit in _upward_imports(p)]
    assert upward == []


@pytest.mark.parametrize("document", DOCUMENTS)
def test_documents_name_only_scripts_that_exist(document):
    paths = sorted(glob.glob(os.path.join(REPO, document)))
    assert paths, document
    missing = []
    for path in paths:
        with open(path) as f:
            text = f.read()
        missing += [
            f"{os.path.relpath(path, REPO)}: {name}"
            for name in sorted(set(_SCRIPT_PATH.findall(text)))
            if not os.path.exists(os.path.join(REPO, name))
        ]
    assert missing == []


def test_slot_replica_prefill_then_decode_at_a_rate_multiple():
    """The three serving lanes' shared behaviour, against hand arithmetic:
    dt 0.5 s, 10 tokens/s a slot, 20 new tokens a request. A cold prefill
    of 1.0 s drains on the tick at t = 0.5 (first token), then 5 tokens a
    tick from t = 1.0: done on the 4th, t = 2.5. A resident one of 0.5 s
    drains at t = 0.0, done at t = 2.0. At twice the rate, 10 tokens a
    tick: done on the 2nd decode tick, t = 1.5."""
    from tpu_engine.twin import SlotReplica

    rep = SlotReplica("r0", slots=4, rate=10.0)
    cold, resident, doubled = ({"n_new": 20} for _ in range(3))
    rep.admit(cold, prefill_s=1.0)
    rep.admit(resident, prefill_s=0.5)
    rep.admit(doubled, prefill_s=1.0, rate_mult=2.0)
    assert rep.free_slots(0.0) == 1
    # No slot decodes yet: the router sees the idle trickle, 0.2 of a slot.
    assert rep.router_stats(0.0) == {
        "tokens_per_sec": 2.0, "free_slots": 1, "slots": 4}

    done: list[dict] = []
    for tick in range(6):
        rep.step(tick * 0.5, 0.5, done)

    assert (cold["first_token_at"], cold["done_at"]) == (0.5, 2.5)
    assert (resident["first_token_at"], resident["done_at"]) == (0.0, 2.0)
    assert (doubled["first_token_at"], doubled["done_at"]) == (0.5, 1.5)
    assert [r is q for r, q in zip(done, (doubled, resident, cold))] == [True] * 3
    assert all(r["replica"] == "r0" for r in done)
    assert rep.tokens_out == 60.0
    assert rep.free_slots(2.5) == 4

    # Not ready yet, or draining: no slot is offered and no time passes.
    late = SlotReplica("r1", slots=4, rate=10.0, ready_at=5.0)
    late.admit({"n_new": 5}, prefill_s=0.0)
    late.step(4.5, 0.5, done)
    assert late.free_slots(4.5) == 0 and late.tokens_out == 0.0
    assert late.free_slots(5.0) == 3
    late.draining = True
    assert late.free_slots(5.0) == 0
