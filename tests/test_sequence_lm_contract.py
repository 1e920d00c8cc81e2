"""The program's side of the contract ``perf/tests/test_manifest.py``
holds for the harness: a new kind of layer adds files and entries only.
``SequenceLM`` asks every kind the same questions in loops, so the six
methods a ``model_config`` PR used to rewrite compare nothing against a
kind's name and test no config key's presence; ``config.describe`` is
the one reader of a family's key names.
"""
import ast
import inspect
import textwrap

import pytest

from ray_tpu.models.sequence_lm import SequenceLM, config

KIND_NAMES = {
    config.LINEAR, config.FULL, config.LATENT, config.MAMBA, config.ATTENTION,
    config.SLIDING, config.DENSE, config.EXPERTS, "plain", "hyper_connection",
    "LINEAR", "FULL", "LATENT", "MAMBA", "ATTENTION", "SLIDING", "DENSE", "EXPERTS",
    "PLAIN", "HYPER",
}
CONFIGS = {"c", "config"}


def _named(node):
    """What a comparison's side names: a constant's value, a name, an
    attribute's last part."""
    if isinstance(node, ast.Constant):
        return node.value
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        return node.attr
    if isinstance(node, (ast.Tuple, ast.List, ast.Set)):
        return tuple(_named(e) for e in node.elts)
    return None


def _offences(fn):
    tree = ast.parse(textwrap.dedent(inspect.getsource(fn)))
    for node in ast.walk(tree):
        if not isinstance(node, ast.Compare):
            continue
        sides = [_named(n) for n in [node.left] + node.comparators]
        flat = {x for s in sides for x in (s if isinstance(s, tuple) else (s,))}
        if flat & KIND_NAMES:
            yield f"line {node.lineno}: compares against a kind"
        if any(isinstance(op, (ast.In, ast.NotIn)) for op in node.ops) and (
                sides[-1] in CONFIGS):
            yield f"line {node.lineno}: tests a config key's presence"


@pytest.mark.parametrize("method", [
    "__init__", "apply", "param_shapes", "init", "initial_state", "reset_state",
    "_rows", "_stack", "_head", "_replay"])
def test_the_model_branches_on_no_kind_and_reads_no_key(method):
    assert list(_offences(getattr(SequenceLM, method))) == []


def test_the_walk_finds_what_it_looks_for():
    def old(self, c, kind):
        if kind == "mamba" or kind in (LINEAR, self.MAMBA):  # noqa: F821
            pass
        return "kv_lora_rank" in c

    assert len(list(_offences(old))) == 3
