#!/usr/bin/env python
"""Generate docs/OPERATORS.md — the operator reference, from the registry
(the role of the reference's generated mxnet.io op docs: every registered
op with its tensor inputs, attrs+defaults, output count, and aliases).

Run: python tools/gen_op_docs.py
"""
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main():
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import incubator_mxnet_tpu  # noqa: F401  (fills the registry)
    from incubator_mxnet_tpu.ops.registry import OP_REGISTRY

    primaries = {}
    alias_of = {}
    for name, opdef in OP_REGISTRY.items():
        if opdef.name == name:
            primaries[name] = opdef
        else:
            alias_of.setdefault(opdef.name, []).append(name)

    lines = [
        "# Operator reference",
        "",
        "GENERATED by `tools/gen_op_docs.py` — do not edit. One row per",
        "registered operator (callable as `nd.<name>` / `sym.<name>`, from",
        "C++ via `mxtpu::ops::<name>`, and by name from every binding).",
        f"{len(primaries)} primary ops, "
        f"{len(OP_REGISTRY) - len(primaries)} aliases.",
        "",
        "| op | inputs | attrs (defaults) | outputs | aliases | summary |",
        "|---|---|---|---|---|---|",
    ]
    for name in sorted(primaries):
        op = primaries[name]
        ins = "variadic" if op.variadic else ", ".join(op.inputs) or "—"
        attrs = ", ".join(
            f"{k}={v!r}" if v is not None else f"{k} (required)"
            for k, v in op.attrs.items()) or "—"
        outs = "dynamic" if callable(op.num_outputs) else str(op.num_outputs)
        aliases = ", ".join(sorted(alias_of.get(name, []))) or "—"
        doc = (op.fn.__doc__ or "").strip().splitlines()
        summary = doc[0].strip() if doc else ""
        row = (f"| `{name}` | {ins} | {attrs} | {outs} | {aliases} "
               f"| {summary} |")
        lines.append(row.replace("\n", " "))
    out = os.path.join(REPO, "docs", "OPERATORS.md")
    with open(out, "w") as f:
        f.write("\n".join(lines) + "\n")
    print(f"wrote {out}: {len(primaries)} ops")


if __name__ == "__main__":
    main()
