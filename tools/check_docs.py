"""Doc drift check: README/docs references to files and symbols must
resolve.

Scans README.md and docs/*.md for

  * markdown links to local files/anchors — the target must exist;
  * backticked path-like references (``src/repro/reduce/api.py``,
    ``examples/multi_device_reduce.py``, ``repro/reduce/policy.py`` —
    with or without the ``src/`` prefix, files or directories);
  * backticked dotted symbols rooted at the package
    (``repro.reduce.collective_mean``,
    ``benchmarks.run``) — the import + attribute chain must resolve;
  * ``path.py::symbol`` pytest-style references — file and attribute
    both checked.

Exits non-zero listing every dangling reference, so CI fails on drift
(e.g. a doc still naming a deleted shim like ``segment_sum_blocked``).

Symbol resolution is shared with the determinism linter
(``repro.analysis.walker``): REQUIRED_SYMBOLS entries must not only
resolve but *originate* under their documented package
(``symbol_origin_ok``), so a symbol that moves modules while a stale
package re-export keeps the old path importable fails here instead of
silently passing.

    PYTHONPATH=src python tools/check_docs.py
"""

from __future__ import annotations

import re
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent

# `python tools/check_docs.py` puts tools/ on sys.path, not the repo root:
# make the documented `repro.*` / `benchmarks.*` symbol resolution work
# regardless of how we were invoked.
for _p in (str(REPO), str(REPO / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from repro.analysis import walker  # noqa: E402

#: files whose references we hold to the resolve-or-fail bar
DOC_FILES = [REPO / "README.md", *sorted((REPO / "docs").glob("*.md"))]

#: load-bearing public API the docs *must* keep naming (and that must
#: keep importing): the contract surface of the exact2 tier, whose one
#: implementation is its policy, run by every face.
#: A rename that forgets the docs — or drops the symbol — fails CI here.
REQUIRED_SYMBOLS = [
    "repro.reduce.policy.Exact2Policy",
    "repro.core.intac.limbs_resolve3_binned",
    "repro.core.intac.limbs_canonical",
    "repro.reduce.collective_mean",
    "repro.reduce.merge_carry_across",
    # the robustness surface (docs/robustness.md): status flags, elastic
    # resume, and the crash-safe checkpoint entry points
    "repro.reduce.ReduceStatus",
    "repro.reduce.elastic_reduce_mean",
    "repro.ckpt.checkpoint.CheckpointError",
    "repro.ckpt.checkpoint.restore_latest_valid",
    # the serving surface (docs/serving.md): continuous batching, paged
    # KV admission, and the paged-gather decode kernel
    "repro.serve.engine.Engine",
    "repro.serve.scheduler.Scheduler",
    "repro.serve.kv_pool.PagedKVPool",
    "repro.kernels.ops.flash_decode_paged",
    # the staged block-program surface (docs/performance.md): the planned
    # program every backend executes, its stage cost hints, and the fused
    # collective the shard merges lower through
    "repro.reduce.BlockProgram",
    "repro.reduce.plan_program",
    "repro.reduce.program.BlockStage",
    "repro.reduce.block_contrib",
    "repro.reduce.fused_psum",
    "benchmarks.roofline.reduce_program_table",
    # the reduction-algebra surface (docs/algebra.md): the op registry,
    # the registered ops, the cascaded time-weighting constructors, and
    # the collective companions of the new ops
    "repro.reduce.ReduceOp",
    "repro.reduce.register_op",
    "repro.reduce.get_op",
    "repro.reduce.algebra.WeightedSumOp",
    "repro.reduce.algebra.SumsqOp",
    "repro.reduce.algebra.MomentsOp",
    "repro.reduce.algebra.PolyOp",
    "repro.reduce.CascadeAccumulator",
    "repro.reduce.poly_weights",
    "repro.reduce.fir_weights",
    "repro.reduce.cascade_weights",
    "repro.reduce.cascade_poly_coeffs",
    "repro.reduce.collective_weighted_mean",
    "repro.reduce.collective_moments",
    # the determinism-lint surface (docs/determinism-lint.md): the AST
    # rules, the jaxpr contract checker, and the shared walker they and
    # this very checker discover/resolve through
    "repro.analysis.run_lint",
    "repro.analysis.Finding",
    "repro.analysis.LintRule",
    "repro.analysis.run_contracts",
    "repro.analysis.walker.iter_source_files",
    "repro.analysis.walker.parse_source",
    "repro.analysis.walker.resolve_symbol",
    "repro.analysis.walker.symbol_origin_ok",
]


def check_required_symbols() -> list:
    """Every REQUIRED_SYMBOLS entry must import, *originate* under its
    documented package (``walker.symbol_origin_ok`` — catches stale
    re-exports after a cross-package move), and be mentioned (by its
    unqualified name) somewhere in the doc set."""
    errors = []
    docs_text = "\n".join(p.read_text() for p in DOC_FILES)
    for ref in REQUIRED_SYMBOLS:
        if not walker.symbol_resolves(ref):
            errors.append(f"required symbol {ref!r} does not resolve")
        elif not walker.symbol_origin_ok(ref):
            errors.append(
                f"required symbol {ref!r} resolves but is defined in "
                f"{walker.symbol_origin(ref)!r} — moved module? update "
                f"the docs and this pin")
        if ref.rsplit(".", 1)[-1] not in docs_text:
            errors.append(f"required symbol {ref!r} is not mentioned in "
                          f"any doc file")
    return errors

_BACKTICK = re.compile(r"`([^`\n]+)`")
_MD_LINK = re.compile(r"\[[^\]]*\]\(([^)\s]+)\)")
_PATHLIKE = re.compile(r"^[\w./-]+(?:\.(?:py|md|txt|yml|toml)|/)$")
_DOTTED = re.compile(r"^(repro|benchmarks)(\.\w+)+$")
_PYTEST_REF = re.compile(r"^([\w./-]+\.py)::(\w+)$")


def _resolve_path(ref: str):
    """The on-disk Path for a doc reference (repo root or src/), or None."""
    ref = ref.rstrip("/")
    for base in (REPO, REPO / "src"):
        if (base / ref).exists():
            return base / ref
    return None


def _path_resolves(ref: str) -> bool:
    return _resolve_path(ref) is not None


def check_file(path: Path) -> list:
    text = path.read_text()
    errors = []

    for m in _MD_LINK.finditer(text):
        target = m.group(1)
        if "://" in target:                     # external URL: out of scope
            continue
        target = target.split("#")[0]
        if target and not (path.parent / target).exists() \
                and not _path_resolves(target):
            errors.append(f"{path.name}: dangling link target {target!r}")

    for m in _BACKTICK.finditer(text):
        ref = m.group(1).strip()
        pytest_ref = _PYTEST_REF.match(ref)
        if pytest_ref:
            fpath, sym = pytest_ref.groups()
            resolved = _resolve_path(fpath)
            if resolved is None:
                errors.append(f"{path.name}: dangling path {fpath!r}")
            elif not re.search(rf"def {sym}\b|class {sym}\b",
                               resolved.read_text()):
                errors.append(f"{path.name}: {fpath!r} has no {sym!r}")
        elif _PATHLIKE.match(ref) and "/" in ref:
            if not _path_resolves(ref):
                errors.append(f"{path.name}: dangling path {ref!r}")
        elif _DOTTED.match(ref):
            if not walker.symbol_resolves(ref):
                errors.append(f"{path.name}: unresolvable symbol {ref!r}")
    return errors


def main() -> int:
    errors = []
    for f in DOC_FILES:
        errors.extend(check_file(f))
    errors.extend(check_required_symbols())
    if errors:
        print(f"doc check: {len(errors)} dangling reference(s)")
        for e in errors:
            print(f"  {e}")
        return 1
    print(f"doc check: {len(DOC_FILES)} files clean "
          f"({', '.join(f.name for f in DOC_FILES)})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
