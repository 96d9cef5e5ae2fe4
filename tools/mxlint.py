#!/usr/bin/env python
"""mxlint — static analyzer for the mxnet_tpu tree.

Level 2 (AST) runs always: traced-host calls in jitted functions,
lock-order cycles, bare excepts, and env-registry discipline over the
given paths (default: the ``mxnet_tpu`` package and ``tools/`` — zero
carve-outs).
Level 3 (whole-repo) also runs always: the shared-mutation race lint
(``repo-shared-mutation`` / ``repo-check-then-act``) and the
wire-contract drift lint (``wire-contract-drift``, driven by the
declared surface registry in ``analysis/contract_lint.py``).
Level 1 (graph) is opt-in via ``--graph``: builds the standard MLP fused
step on a dp mesh (8 virtual CPU devices) and lints its program —
donation coverage, host callbacks, the collective audit, dtype drift.

Exit codes: 0 = clean, 1 = findings, 2 = internal/usage error.

Reports: human lines on stdout; ``--json PATH`` (or the
``MXTPU_ANALYZE_REPORT`` env var) writes the stable machine-readable
report CI diffs across commits (see
docs/how_to/static_analysis.md).  Suppress a finding inline with
``# mxlint: disable=<rule>`` on (or above) the offending line.

    tools/mxlint.py                      # lint the tree
    tools/mxlint.py --changed            # only files changed vs HEAD
    tools/mxlint.py --self               # lint the linter too
    tools/mxlint.py --graph --json r.json mxnet_tpu
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import os
import subprocess
import sys
import time
import types

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_ANALYSIS_DIR = os.path.join(_REPO, "mxnet_tpu", "analysis")


def _load_ast_level():
    """Load report.py + the lint passes by file path under a synthetic
    package, WITHOUT importing mxnet_tpu — the AST level is stdlib-only
    by design, and this CLI must work (and stay side-effect-free) in
    containers with no jax/accelerator runtime and in launch-configured
    environments where importing the package would auto-join a
    distributed process group."""
    pkg = types.ModuleType("_mxlint_analysis")
    pkg.__path__ = [_ANALYSIS_DIR]
    sys.modules.setdefault("_mxlint_analysis", pkg)

    def load(modname):
        fullname = "_mxlint_analysis." + modname
        if fullname in sys.modules:
            return sys.modules[fullname]
        spec = importlib.util.spec_from_file_location(
            fullname, os.path.join(_ANALYSIS_DIR, modname + ".py"))
        mod = importlib.util.module_from_spec(spec)
        sys.modules[fullname] = mod
        spec.loader.exec_module(mod)
        return mod

    load("report")
    return load("ast_lint"), load("race_lint"), load("contract_lint")


def _graph_lint_mlp():
    """Build the standard 2-layer MLP fused step on a dp mesh and lint
    it (the same model tier-1 regression tests pin) — proving the
    shipped trainer's program donates its carries, syncs nothing to the
    host, and emits only the expected dp all-reduces.  The ONLY mode
    that imports the package (and jax)."""
    if _REPO not in sys.path:
        sys.path.insert(0, _REPO)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    from mxnet_tpu.analysis import fixtures

    trainer = fixtures.standard_mlp_trainer()
    try:
        return trainer.analyze(*fixtures.standard_mlp_batch())
    finally:
        trainer.close()


def _default_paths():
    """The zero-carve-out lint scope: the package and the tools."""
    return [os.path.join(_REPO, "mxnet_tpu"),
            os.path.join(_REPO, "tools")]


def _changed_paths(ref):
    """Python files changed vs ``ref`` per git (the pre-commit loop's
    sub-second scope).  Returns None when not in a git checkout (caller
    falls back to the full tree)."""
    try:
        out = subprocess.run(
            ["git", "diff", "--name-only", ref, "--", "*.py"],
            cwd=_REPO, capture_output=True, text=True, timeout=30)
    except (OSError, subprocess.TimeoutExpired):
        return None
    if out.returncode != 0:
        return None
    paths = []
    for line in out.stdout.splitlines():
        full = os.path.join(_REPO, line.strip())
        if line.strip() and os.path.isfile(full):
            paths.append(full)
    return paths


def _grep(path, needles):
    try:
        with open(path, "r", encoding="utf-8") as f:
            text = f.read()
    except OSError:
        return False
    return any(n in text for n in needles)


def _mentions_env(paths):
    """Cheap text probe: does any changed file touch the env-registry
    machinery (the only rules that need the package-wide registry)?"""
    return any(_grep(p, ("get_env", "getenv", "environ", "register_env"))
               for p in paths)


def _registry_sources():
    """Package files that can declare env knobs (contain a
    ``register_env`` call) — a text prefilter so --changed mode parses
    a handful of files for the registry instead of the whole package."""
    out = []
    for root, _dirs, files in os.walk(os.path.join(_REPO, "mxnet_tpu")):
        for name in files:
            if name.endswith(".py"):
                full = os.path.join(root, name)
                if _grep(full, ("register_env",)):
                    out.append(full)
    return out


def _touches_surfaces(contract_lint, paths):
    """Does any changed file participate in a declared wire surface
    (producer, consumer, or the fault namespace, which spans the whole
    tree)?"""
    refs = set()
    for surface in contract_lint.repo_registry():
        if surface.kind == "faults":
            # fault armings can live anywhere — any changed file counts
            return bool(paths)
        for relpath, _q in tuple(surface.producers) + tuple(
                surface.consumers):
            refs.add(os.path.normpath(os.path.join(_REPO, relpath)))
    return any(os.path.normpath(os.path.abspath(p)) in refs
               for p in paths)


def main(argv=None):
    parser = argparse.ArgumentParser(
        prog="mxlint", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    parser.add_argument("paths", nargs="*",
                        help="files/directories to lint (default: the "
                             "mxnet_tpu package + tools/)")
    parser.add_argument("--self", dest="lint_self", action="store_true",
                        help="lint the linter (tools/mxlint.py + the "
                             "analysis package) along with the package")
    parser.add_argument("--changed", nargs="?", const="HEAD",
                        default=None, metavar="REF",
                        help="lint only .py files in `git diff "
                             "--name-only REF` (default HEAD); falls "
                             "back to the full tree outside a git "
                             "checkout.  The contract pass stays "
                             "repo-global either way (its registry "
                             "pulls in both sides of every surface)")
    parser.add_argument("--graph", action="store_true",
                        help="also graph-lint the standard MLP fused "
                             "step (compiles a small program)")
    parser.add_argument("--json", dest="json_path", default=None,
                        help="write the machine-readable report here "
                             "(default: $MXTPU_ANALYZE_REPORT if set)")
    parser.add_argument("--select", "--rules", dest="select",
                        default=None,
                        help="comma-separated rule subset to run")
    parser.add_argument("--list-faults", action="store_true",
                        help="print the fault-point registry (every "
                             "statically resolvable faults.maybe_* "
                             "site under the paths) and exit — the "
                             "mechanical source for docs/how_to/"
                             "fault_tolerance.md's list")
    parser.add_argument("-q", "--quiet", action="store_true",
                        help="suppress the human report (exit code and "
                             "--json only)")
    args = parser.parse_args(argv)

    t0 = time.monotonic()
    try:
        ast_lint, race_lint, contract_lint = _load_ast_level()
    except Exception as e:  # noqa: BLE001 — report, don't traceback
        sys.stderr.write("mxlint: cannot load the analysis modules: %s\n"
                         % (e,))
        return 2

    paths = list(args.paths)
    if not paths:
        paths = _default_paths()
    changed_mode = False
    if args.changed is not None and not args.paths:
        changed = _changed_paths(args.changed)
        if changed is not None:
            paths = changed
            changed_mode = True
    if args.list_faults:
        points = ast_lint.collect_fault_points(paths)
        for name in sorted(points):
            sites = ", ".join(
                "%s:%d" % (os.path.relpath(f, _REPO), line)
                for f, line, _ in points[name])
            print("%-22s %s" % (name, sites))
        print("mxlint: %d fault point(s)" % len(points))
        return 0
    if args.lint_self:
        paths.append(os.path.abspath(__file__))

    all_rules = tuple(ast_lint.RULES) + tuple(race_lint.RULES) + \
        tuple(contract_lint.RULES)
    select = None
    if args.select:
        select = [r.strip() for r in args.select.split(",") if r.strip()]
        unknown = set(select) - set(all_rules)
        if unknown:
            sys.stderr.write("mxlint: unknown rule(s) %s (known: %s)\n"
                             % (sorted(unknown), ", ".join(all_rules)))
            return 2

    # one parse per file, shared by every pass (and by the env-registry
    # collection below when the package is inside the lint scope)
    cache = {}
    # the registry, collected STATICALLY from the package (register_env
    # call literals) so linting paths outside it — this file, example
    # scripts — still knows every declared knob without importing
    # anything.  In --changed mode the package-wide collection is the
    # dominant cost, so it is skipped unless a changed file actually
    # touches the env machinery (the rules that need the registry can
    # only fire on such a file).
    registry = None
    if not changed_mode:
        registry = ast_lint.collect_registered(
            [os.path.join(_REPO, "mxnet_tpu")], cache=cache)
    elif _mentions_env(paths):
        registry = ast_lint.collect_registered(
            _registry_sources(), cache=cache)

    report = ast_lint.lint_paths(paths, env_registry=registry,
                                 select=select, cache=cache)
    extras = [race_lint.lint_paths(paths, select=select, cache=cache)]
    # the contract pass is repo-global (it pulls in both sides of every
    # declared surface); in --changed mode it can only change verdict
    # when a changed file participates in some surface, so skip it
    # otherwise and keep the pre-commit loop sub-second
    if not changed_mode or _touches_surfaces(contract_lint, paths):
        extras.append(contract_lint.lint_paths(paths, select=select,
                                               cache=cache))
    for extra in extras:
        extra.files_scanned = 0       # same files, already counted
        report.merge(extra)
    if args.graph:
        try:
            report.merge(_graph_lint_mlp())
        except Exception as e:  # noqa: BLE001 — device bring-up varies
            sys.stderr.write("mxlint: graph level failed to run: %s\n"
                             % (e,))
            return 2
    elapsed = time.monotonic() - t0

    # read directly: this CLI must not import the package for get_env
    json_path = args.json_path or \
        os.environ.get("MXTPU_ANALYZE_REPORT")  # mxlint: disable=env-direct-read
    if json_path:
        payload = report.to_dict()
        # timing lives OUTSIDE the diffable findings/summary contract
        payload["elapsed_s"] = round(elapsed, 3)
        with open(json_path, "w") as f:
            json.dump(payload, f, indent=2, sort_keys=True)
            f.write("\n")
    if not args.quiet:
        print(report.format_text())
        print("mxlint: %.2fs" % elapsed)
    return 1 if report.findings else 0


if __name__ == "__main__":
    sys.exit(main())
