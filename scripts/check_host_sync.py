"""Static check: serving code never syncs the device inside a host loop,
and never syncs inside a ``launch`` body (the overlap-killing pattern).

The serving engine's whole perf story is dispatch amortization — one
device round-trip per TICK (the fused decode tick pays one per
``decode_steps_per_tick`` tokens).  A ``np.asarray(...)`` /
``.block_until_ready()`` / ``jax.device_get(...)`` call INSIDE a ``for``
or ``while`` loop under ``tpu_parallel/serving/`` is the tell-tale of a
per-slot (or per-item) device sync: each iteration stalls the host on
the device pipeline, once a token at batch 1.  Tick-BOUNDARY syncs — one per engine tick, before
the host unpacks a token block — are the intended pattern and sit
outside loops by construction; a loop that genuinely needs one (e.g. the
standalone speculative host loop, which syncs once per verify tick)
annotates the line with ``# host-sync: <why>`` and is whitelisted.

The LAUNCH rule: the engine's double-buffered tick splits into
``launch()`` (dispatch, no sync) and ``collect()`` (one sync +
delivery), so tick N's host bookkeeping can overlap tick N+1's device
compute.  ONE sync anywhere on the launch side serializes the pipeline
— the host stalls before the next tick is even dispatched and the
overlap ratio silently collapses to zero.  So any device-sync call
inside a function named ``launch`` or ``_launch*`` under
``tpu_parallel/serving/``, or inside a function of the same file that
one of those reaches through ``self.<name>(...)`` / ``<name>(...)``
calls (admission, the prefill, the chunk block: ``step()`` launches
tick N+1 before it reads tick N, so a sync anywhere on that side idles
the device), flags, loop or no loop (same ``# host-sync:`` whitelist
for a justified exception).

Like ``check_clock.py`` (the injectable-clock contract) this turns a
prose rule into a tier-1 test
(``tests/test_cluster.py::test_serving_no_per_slot_host_sync`` and the
``check_all`` registry).  The check is LEXICAL within one file: it sees
calls written inside loop bodies and inside the functions the launch
side reaches by name in that file, not syncs reached through another
object (``self.pool.<...>``) or another module — the gated debug fetch
in ``CachePool.assert_slot_aligned`` (called per slot under
``spec_check_invariants=True``) is out of scope by design.

Usage: ``python scripts/check_host_sync.py [paths...]`` — prints one
``file:line: <call> syncs the device ...`` per violation, exits nonzero
on any.
"""

from __future__ import annotations

import ast
import os
import sys
from typing import List

# device-sync reads: np/numpy.asarray + np/numpy.array materialize a jax
# array on the host; .block_until_ready() and jax.device_get() are
# explicit fences
SYNC_ATTRS = frozenset({"asarray", "array"})
SYNC_MODULES = frozenset({"np", "numpy"})
FENCE_ATTRS = frozenset({"block_until_ready", "device_get"})

# obs/ holds the completion clock (obs/device_clock.py): its wait for a
# program's output is the one sanctioned read off the pump thread
DEFAULT_PATHS = ("tpu_parallel/serving", "tpu_parallel/fleet", "tpu_parallel/obs")

WHITELIST_MARK = "# host-sync:"


def _flag_of(node: ast.Call) -> str | None:
    func = node.func
    if isinstance(func, ast.Attribute):
        if (
            func.attr in SYNC_ATTRS
            and isinstance(func.value, ast.Name)
            and func.value.id in SYNC_MODULES
        ):
            return f"{func.value.id}.{func.attr}"
        if func.attr in FENCE_ATTRS:
            return f"<...>.{func.attr}"
    return None


def _is_launch_name(name: str) -> bool:
    """Function names the launch rule covers: the engine's public
    ``launch`` and its ``_launch_*`` dispatch helpers."""
    return name == "launch" or name.startswith("_launch")


def _launch_side(tree: ast.AST) -> frozenset:
    """Names of the file's functions that run on the launch side: the
    launch-named ones, and whatever they reach through ``self.<name>()``
    or ``<name>()`` calls to functions defined in the same file."""
    defs = {}
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            defs.setdefault(node.name, []).append(node)

    def callees(fn: ast.AST):
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            func = node.func
            if isinstance(func, ast.Name):
                yield func.id
            elif (
                isinstance(func, ast.Attribute)
                and isinstance(func.value, ast.Name)
                and func.value.id == "self"
            ):
                yield func.attr

    reached = {name for name in defs if _is_launch_name(name)}
    frontier = list(reached)
    while frontier:
        for fn in defs[frontier.pop()]:
            for name in callees(fn):
                if name in defs and name not in reached:
                    reached.add(name)
                    frontier.append(name)
    return frozenset(reached)


def check_source(source: str, filename: str) -> List[str]:
    """Return ``file:line: message`` strings for every device-sync call
    lexically inside a ``for``/``while`` body or a comprehension's
    per-iteration positions, OR anywhere inside a ``launch``/``_launch*``
    function body or a function of the file those reach (the
    launch/collect overlap contract), minus lines carrying the
    ``# host-sync: <why>`` whitelist annotation."""
    tree = ast.parse(source, filename=filename)
    launch_side = _launch_side(tree)
    lines = source.splitlines()
    problems: List[str] = []

    def flag(node: ast.Call, in_launch: bool) -> None:
        flagged = _flag_of(node)
        if flagged is None:
            return
        # the annotation may land on any physical line of a wrapped call
        # (black puts the closing paren — and the trailing comment — on
        # its own line), so scan the call's whole lineno..end_lineno span
        span = lines[node.lineno - 1 : (node.end_lineno or node.lineno)]
        if any(WHITELIST_MARK in line for line in span):
            return
        if in_launch:
            problems.append(
                f"{filename}:{node.lineno}: {flagged}() syncs the "
                "device inside a launch body (the overlap-killing "
                "pattern — launch dispatches, collect syncs; move it "
                "to the collect side, or annotate "
                "'# host-sync: <why>')"
            )
        else:
            problems.append(
                f"{filename}:{node.lineno}: {flagged}() syncs the "
                "device inside a host loop (per-slot sync — hoist "
                "to the tick boundary, or annotate "
                "'# host-sync: <why>')"
            )

    def walk(node: ast.AST, in_loop: bool, in_launch: bool) -> None:
        if isinstance(node, ast.Call) and (in_loop or in_launch):
            flag(node, in_launch)
        if isinstance(
            node, (ast.ListComp, ast.SetComp, ast.DictComp, ast.GeneratorExp)
        ):
            # comprehensions are loops too: the element expression, the
            # `if` clauses, and every generator after the first run PER
            # ITERATION; only the FIRST generator's iterable evaluates
            # once (so `np.asarray(x)` as the thing being iterated stays
            # legal while `[np.asarray(f(s)) for s in slots]` flags)
            walk(node.generators[0].iter, in_loop, in_launch)
            for i, gen in enumerate(node.generators):
                if i > 0:
                    walk(gen.iter, True, in_launch)
                walk(gen.target, True, in_launch)
                for cond in gen.ifs:
                    walk(cond, True, in_launch)
            if isinstance(node, ast.DictComp):
                walk(node.key, True, in_launch)
                walk(node.value, True, in_launch)
            else:
                walk(node.elt, True, in_launch)
            return
        enter_loop = in_loop or isinstance(node, (ast.For, ast.While))
        enter_launch = in_launch
        # a nested function DEF inside a loop body is not executed per
        # iteration at its definition site's cost — but calls inside it
        # are only flagged if ITS body contains a loop of its own, so
        # reset the loop context at function boundaries.  The launch
        # context instead TURNS ON at a launch-named def and stays on
        # for nested defs/lambdas (they run on the launch side too).
        if isinstance(
            node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)
        ):
            enter_loop = False
            if not isinstance(node, ast.Lambda) and (
                node.name in launch_side
            ):
                enter_launch = True
        for child in ast.iter_child_nodes(node):
            walk(child, enter_loop, enter_launch)

    walk(tree, False, False)
    return problems


def check_paths(paths=DEFAULT_PATHS) -> List[str]:
    problems: List[str] = []
    for path in paths:
        if not os.path.exists(path):
            # a typo'd path must not walk zero files and report OK
            raise FileNotFoundError(f"check_host_sync: no such path: {path}")
        if os.path.isfile(path):
            files = [path]
        else:
            files = sorted(
                os.path.join(root, f)
                for root, _, names in os.walk(path)
                for f in names
                if f.endswith(".py")
            )
        for fname in files:
            with open(fname) as fh:
                problems.extend(check_source(fh.read(), fname))
    return problems


def main(argv: List[str]) -> int:
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    os.chdir(repo_root)
    paths = argv[1:] or list(DEFAULT_PATHS)
    problems = check_paths(paths)
    for problem in problems:
        print(problem, file=sys.stderr)
    if problems:
        print(
            f"check_host_sync: {len(problems)} per-slot device sync(s)",
            file=sys.stderr,
        )
        return 1
    print("check_host_sync: OK")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
