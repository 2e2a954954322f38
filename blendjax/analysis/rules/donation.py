"""BJX112 non-donated-train-jit: a step-like jit in a driver hot path
that doesn't donate its state argument.

Every train-step jit in the hot path donates the state
(``donate_argnums=(0,)``): the donated update writes the new
params/optimizer state back into the buffers it consumed, so the
run's device memory is ONE copy of the state instead of two and no
per-step reallocation happens (the runtime donation audit,
:mod:`blendjax.testing.donation`, pins the pointer-stability this
buys). A
``jax.jit`` on a step-like function that OMITS the donation keyword
silently doubles state memory and re-allocates every step — it still
trains correctly, which is exactly why it needs a lint, not a test.

Scope: driver-hot-path modules — the ``bjx: driver-hot-path`` marker
comment or a ``driver.py`` basename (as BJX106/BJX108) plus
``steps.py``/``mesh_driver.py``, where the step builders live.
"Step-like" follows the repo's naming convention: the jitted
function's name carries a ``step``/``fused``/``train`` segment
(underscore-anchored, so ``constraint`` never reads as ``train``), or
its first parameter is named ``state``/``st``/``train_state``. Both call
form (``jax.jit(step, ...)``) and decorator form (``@jax.jit``) are
checked. An intentionally donation-free jit (a pure evaluator that
only READS the state) suppresses with ``# bjx: ignore[BJX112]`` and a
justification — ``make_eval_step`` is the canonical example.

Note the rule checks for the donation keyword's PRESENCE, not its
value: ``donate_argnums=(0,) if donate else ()`` is a deliberate,
visible opt-out knob, which is the thing the rule exists to force.
"""

from __future__ import annotations

import ast
import os
import re
from typing import Iterator

from blendjax.analysis.core import (
    Finding,
    ModuleContext,
    Rule,
    register,
)
from blendjax.analysis.rules.driver_sync import _is_driver_hot

STEP_MODULE_BASENAMES = {"steps.py", "mesh_driver.py"}
# segment-anchored, not bare substrings: 'constraint'/'constrain'/
# 'strain' must not read as train, while step/_fused/train_step/
# make_echo_fused_step all still hit
STEP_NAME_RE = re.compile(r"(?:^|_)(?:step|fused|train)", re.IGNORECASE)
STATE_PARAM_NAMES = {"state", "st", "train_state"}
DONATE_KEYWORDS = {"donate_argnums", "donate_argnames"}


def _in_scope(module: ModuleContext) -> bool:
    if os.path.basename(module.relpath) in STEP_MODULE_BASENAMES:
        return True
    return _is_driver_hot(module)


def _function_defs(module: ModuleContext) -> dict[str, ast.AST]:
    """Every function/lambda-free def in the module by BARE name (the
    innermost def wins ties — jit sites reference the local one)."""
    defs: dict[str, ast.AST] = {}
    for _qual, fn, _cls in module.iter_functions():
        defs[fn.name] = fn
    return defs


def _first_param(fn: ast.AST | None) -> str | None:
    args = getattr(fn, "args", None)
    if args is None:
        return None
    pos = list(args.posonlyargs) + list(args.args)
    if not pos:
        return None
    first: str = pos[0].arg
    if first in ("self", "cls") and len(pos) > 1:
        return str(pos[1].arg)
    return first


def _is_step_like(name: str | None, fn: ast.AST | None) -> bool:
    if name and STEP_NAME_RE.search(name):
        return True
    if fn is not None:
        first = _first_param(fn)
        if first and first.lower() in STATE_PARAM_NAMES:
            return True
    return False


def _is_jit(module: ModuleContext, func: ast.AST) -> bool:
    resolved = module.resolve(func) or ""
    return resolved == "jax.jit" or resolved.endswith("jax.jit")


@register
class NonDonatedTrainJitRule(Rule):
    id = "BJX112"
    name = "non-donated-train-jit"
    description = (
        "jax.jit on a step-like function in a driver hot path without "
        "donate_argnums/donate_argnames for the state argument"
    )

    def check(self, module: ModuleContext) -> Iterator[Finding]:
        if not _in_scope(module):
            return
        defs = _function_defs(module)
        # call form: jax.jit(fn, ...)
        for node in ast.walk(module.tree):
            if isinstance(node, ast.Call) and _is_jit(module, node.func):
                yield from self._check_call(module, node, defs)
        # decorator form: @jax.jit on a def
        for _qual, fn, _cls in module.iter_functions():
            for deco in fn.decorator_list:
                target = deco.func if isinstance(deco, ast.Call) else deco
                if not _is_jit(module, target):
                    continue
                kws = (
                    {k.arg for k in deco.keywords}
                    if isinstance(deco, ast.Call) else set()
                )
                if kws & DONATE_KEYWORDS:
                    continue
                if _is_step_like(fn.name, fn):
                    yield self._finding(module, deco, fn.name)

    def _check_call(
        self, module: ModuleContext, node: ast.Call,
        defs: dict[str, ast.AST],
    ) -> Iterator[Finding]:
        if {k.arg for k in node.keywords} & DONATE_KEYWORDS:
            return
        if not node.args:
            return
        target = node.args[0]
        name: str | None
        fn: ast.AST | None
        if isinstance(target, ast.Name):
            name = target.id
            fn = defs.get(name)
        elif isinstance(target, ast.Lambda):
            name = None
            fn = target
        else:
            return  # attribute/call targets: out of the heuristic's reach
        if _is_step_like(name, fn):
            yield self._finding(module, node, name or "<lambda>")

    def _finding(
        self, module: ModuleContext, node: ast.AST, name: str
    ) -> Finding:
        return self.finding(
            module,
            node,
            f"jax.jit on step-like '{name}' omits donate_argnums for "
            "the state argument — the un-donated update doubles state "
            "memory and reallocates it every step; donate the state "
            "(or suppress with a justification if the jit only READS "
            "it)",
        )
