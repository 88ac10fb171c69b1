#!/usr/bin/env python
"""Generate jvm-package Ops.java from the op registry.

Reference role: scala-package's macro-generated NDArray op bindings (built
from MXSymbolGetAtomicSymbolInfo). Each registered op with a Java-safe name
gets a static wrapper taking its tensor inputs plus an optional AttrMap;
multi-output ops return NDArray[].

Run: python tools/gen_jvm_api.py
"""
import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

JAVA_KEYWORDS = {
    "abstract", "assert", "boolean", "break", "byte", "case", "catch",
    "char", "class", "const", "continue", "default", "do", "double", "else",
    "enum", "extends", "final", "finally", "float", "for", "goto", "if",
    "implements", "import", "instanceof", "int", "interface", "long",
    "native", "new", "package", "private", "protected", "public", "return",
    "short", "static", "strictfp", "super", "switch", "synchronized",
    "this", "throw", "throws", "transient", "try", "void", "volatile",
    "while", "true", "false", "null",
}

IDENT = re.compile(r"^[A-Za-z_][A-Za-z0-9_]*$")


def jname(name):
    if not IDENT.match(name):
        return None
    return name + "_" if name in JAVA_KEYWORDS else name


def gen_op(name, opdef):
    mname = jname(name)
    if mname is None:
        return None
    multi = callable(opdef.num_outputs) or opdef.num_outputs != 1
    ret = "NDArray[]" if multi else "NDArray"
    call = "MXTpu.invoke" if multi else "MXTpu.invoke1"
    if opdef.variadic:
        sig = f"  public static {ret} {mname}(NDArray[] data, AttrMap attrs) {{"
        body = f"    return {call}(\"{name}\", data, attrs);"
        lines = [sig, body, "  }"]
        # convenience overload without attrs
        lines += [
            f"  public static {ret} {mname}(NDArray[] data) {{",
            f"    return {mname}(data, null);",
            "  }",
        ]
        return "\n".join(lines)
    params = []
    for inp in opdef.inputs:
        pn = jname(inp) or "in" + re.sub(r"\W", "_", inp)
        params.append((inp, pn))
    plist = ", ".join(f"NDArray {pn}" for _, pn in params)
    array = ", ".join(pn for _, pn in params)
    lines = [
        f"  public static {ret} {mname}({plist}"
        + (", " if params else "") + "AttrMap attrs) {",
        f"    return {call}(\"{name}\", new NDArray[] {{{array}}}, attrs);",
        "  }",
    ]
    if params:  # overload without attrs
        lines += [
            f"  public static {ret} {mname}({plist}) {{",
            f"    return {mname}({array}, null);",
            "  }",
        ]
    req = [(inp, pn) for inp, pn in params if inp not in opdef.optional]
    if len(req) < len(params):
        # required-only overloads forwarding null for the optional tensor
        # inputs (parity with the C++ header's defaulted NDArray() args)
        plist_req = ", ".join(f"NDArray {pn}" for _, pn in req)
        fwd = ", ".join(pn if inp not in opdef.optional else "null"
                        for inp, pn in params)
        lines += [
            f"  public static {ret} {mname}({plist_req}"
            + (", " if req else "") + "AttrMap attrs) {",
            f"    return {mname}({fwd}, attrs);",
            "  }",
        ]
        if req:
            array_req = ", ".join(pn for _, pn in req)
            lines += [
                f"  public static {ret} {mname}({plist_req}) {{",
                f"    return {mname}({array_req}, (AttrMap) null);",
                "  }",
            ]
    return "\n".join(lines)


def main():
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from incubator_mxnet_tpu.ops.registry import OP_REGISTRY

    blocks, skipped = [], []
    for name in sorted(OP_REGISTRY):
        opdef = OP_REGISTRY[name]
        if opdef.name != name:
            continue
        b = gen_op(name, opdef)
        (blocks if b else skipped).append(b or name)

    out = os.path.join(REPO, "jvm-package", "src", "main", "java", "org",
                       "apache", "mxtpu", "Ops.java")
    with open(out, "w") as f:
        f.write(
            "package org.apache.mxtpu;\n\n"
            "/**\n"
            " * GENERATED by tools/gen_jvm_api.py — DO NOT EDIT.\n"
            " * Static op wrappers over the imperative runtime (reference\n"
            " * role: scala-package's generated NDArray ops).\n"
            f" * {len(blocks)} ops.\n"
            " */\n"
            "@SuppressWarnings({\"checkstyle:MethodName\", "
            "\"checkstyle:ParameterName\"})\n"
            "public final class Ops {\n"
            "  private Ops() {}\n\n")
        f.write("\n\n".join(blocks))
        f.write("\n}\n")
    print(f"wrote {out}: {len(blocks)} ops, {len(skipped)} skipped")


if __name__ == "__main__":
    main()
