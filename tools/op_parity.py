#!/usr/bin/env python
"""Op-parity audit: every operator the reference registers, classified.

Scans /root/reference/src/operator for NNVM_REGISTER_OP /
MXNET_OPERATOR_REGISTER_* / MXNET_REGISTER_IMAGE_AUG_OP /
MXNET_REGISTER_OP_PROPERTY registrations, diffs the name set against this
framework's OP_REGISTRY (+ aliases + non-registry surfaces), and writes
OPS_PARITY.md.  Exits non-zero if any reference op is left unclassified —
the artifact the round-4 review asked for.

Usage:  JAX_PLATFORMS=cpu python tools/op_parity.py
"""
from __future__ import annotations

import os
import re
import sys

REF_OP_DIR = "/root/reference/src/operator"
HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)

_REG_RE = re.compile(
    r"(?:NNVM_REGISTER_OP|MXNET_OPERATOR_REGISTER[A-Z_]*|"
    r"MXNET_REGISTER_IMAGE[A-Z_]*|MXNET_REGISTER_OP_PROPERTY)"
    r"\((_?[A-Za-z0-9_]+)[,)]")

# macro-definition formal parameters the regex would otherwise pick up
_ARTIFACTS = {"name", "distr"}


def reference_ops():
    names = set()
    for root, _dirs, files in os.walk(REF_OP_DIR):
        for fn in files:
            if not fn.endswith((".cc", ".cu", ".h", ".cuh")):
                continue
            with open(os.path.join(root, fn), errors="replace") as f:
                text = f.read()
            for m in _REG_RE.finditer(text):
                names.add(m.group(1))
    return names - _ARTIFACTS


# ---------------------------------------------------------------------------
# classification of reference ops that are NOT verbatim registry names
# ---------------------------------------------------------------------------

# reference internal name -> the surface carrying the same capability here
ALIASES = {
    # linalg ops register as _linalg_* and alias to the public linalg_*
    # namespace in the reference (src/operator/tensor/la_op.cc); this
    # framework registers the public names directly
    **{f"_linalg_{n}": f"linalg_{n}" for n in
       ("gelqf", "gemm", "gemm2", "potrf", "potri", "sumlogdiag", "syevd",
        "syrk", "trmm", "trsm")},
    # control flow are first-class symbol/eager constructs here, not
    # registry entries (symbol/control_flow.py: foreach/while_loop/cond
    # with tojson round-trip; reference src/operator/control_flow.cc)
    "_foreach": "mxnet_tpu.symbol.control_flow.foreach",
    "_while_loop": "mxnet_tpu.symbol.control_flow.while_loop",
    "_cond": "mxnet_tpu.symbol.control_flow.cond",
    # cross-device copies are inserted by the placement pass
    # (symbol/placement.py, group2ctx), not dispatched as an op
    # (reference src/operator/cross_device_copy.cc)
    "_CrossDeviceCopy": "mxnet_tpu.symbol.placement (device_put edges)",
}

NA_RULES = [
    (re.compile(r"^_backward_.*$"),
     "gradient node: JAX autodiff derives VJPs from the forward emitter; "
     "no separate backward op registration exists by design"),
    (re.compile(r"^_broadcast_backward$"),
     "gradient helper for broadcast ops; folded into JAX VJPs"),
    (re.compile(r"^_contrib_backward_quadratic$"),
     "gradient node of contrib quadratic; folded into JAX VJPs"),
    (re.compile(r"^CuDNN.*$"),
     "cuDNN vendor wrapper; XLA owns kernel selection on TPU (SURVEY §2.1 "
     "cuDNN row is n/a)"),
    (re.compile(r"^_trt_op$"),
     "TensorRT subgraph op (NVIDIA inference runtime); XLA compilation is "
     "the TPU equivalent"),
    (re.compile(r"^_(NDArray|Native)$"),
     "deprecated pre-Custom python-op bridges (reference "
     "src/operator/custom/ndarray_op.cc, native_op.cc); superseded by the "
     "Custom op bridge, which mxnet_tpu/operator.py implements"),
]


def our_surface():
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    from mxnet_tpu.ops.registry import OP_REGISTRY

    names = set(OP_REGISTRY)
    for op in OP_REGISTRY.values():
        names.update(getattr(op, "aliases", ()) or ())
    return names


def classify(write_md=True):
    ref = reference_ops()
    ours = our_surface()
    implemented, aliased, na, unclassified = [], [], [], []
    for name in sorted(ref):
        if name in ours:
            implemented.append(name)
            continue
        if name in ALIASES:
            target = ALIASES[name]
            ok = target in ours or "." in target or " " in target
            (aliased if ok else unclassified).append(name)
            continue
        for rx, reason in NA_RULES:
            if rx.match(name):
                na.append((name, reason))
                break
        else:
            unclassified.append(name)

    if write_md:
        lines = [
            "# OPS_PARITY — reference operator registrations, classified",
            "",
            "Generated by `tools/op_parity.py` (re-run to refresh).  Source "
            "of truth: every `NNVM_REGISTER_OP` / `MXNET_OPERATOR_REGISTER_*` "
            "/ `MXNET_REGISTER_OP_PROPERTY` name under "
            "`/root/reference/src/operator/` vs `mxnet_tpu.ops.registry."
            "OP_REGISTRY` (+ aliases + symbol-level surfaces).",
            "",
            f"| class | count |",
            f"|---|---|",
            f"| reference ops total | {len(ref)} |",
            f"| implemented (verbatim name in registry) | {len(implemented)} |",
            f"| aliased (capability under another surface) | {len(aliased)} |",
            f"| n/a with reason | {len(na)} |",
            f"| **unclassified** | **{len(unclassified)}** |",
            "",
            "## Aliased",
            "",
            "| reference op | surface here |",
            "|---|---|",
        ]
        lines += [f"| `{n}` | `{ALIASES[n]}` |" for n in aliased]
        lines += ["", "## N/A (with reason)", ""]
        # group the backward ops into one row; list the rest individually
        bwd = [n for n, _ in na if n.startswith("_backward_")]
        rest = [(n, r) for n, r in na if not n.startswith("_backward_")]
        lines += ["| reference op | reason |", "|---|---|"]
        if bwd:
            lines.append(
                f"| `_backward_*` ({len(bwd)} ops) | {NA_RULES[0][1]} |")
        lines += [f"| `{n}` | {r} |" for n, r in rest]
        if unclassified:
            lines += ["", "## UNCLASSIFIED (must be empty)", ""]
            lines += [f"- `{n}`" for n in unclassified]
        lines += ["", f"Implemented list ({len(implemented)} names) is the "
                  "registry itself; enumerate with "
                  "`python -c \"from mxnet_tpu.ops.registry import "
                  "OP_REGISTRY; print(sorted(OP_REGISTRY))\"`.", ""]
        with open(os.path.join(REPO, "OPS_PARITY.md"), "w") as f:
            f.write("\n".join(lines))
    return implemented, aliased, na, unclassified


def main():
    implemented, aliased, na, unclassified = classify()
    print(f"reference ops: {len(implemented) + len(aliased) + len(na) + len(unclassified)}")
    print(f"implemented {len(implemented)}, aliased {len(aliased)}, "
          f"n/a {len(na)}, unclassified {len(unclassified)}")
    if unclassified:
        print("UNCLASSIFIED:", ", ".join(unclassified))
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
