#!/usr/bin/env python
"""Generate include/mxtpu_ops.hpp — C++ op wrappers from the op registry.

Reference role: cpp-package's generated include/mxnet-cpp/op.h (built by
OpWrapperGenerator.py from MXSymbolGetAtomicSymbolInfo). Here the source of
truth is ops.OP_REGISTRY: every registered op whose name is a valid C++
identifier gets an inline wrapper in namespace mxtpu::ops that JSON-encodes
its attrs and dispatches through the embedded imperative runtime
(include/mxtpu_imperative.hpp, src/imperative.cc).

Attr typing: every attribute is `const Attr&` (a JSON-able variant with
implicit conversions from bool/int/double/string/brace-lists), defaulting
to "unset" so the op's Python-side default applies. The generated doc
comment shows each attr's Python default.

Run: python tools/gen_cpp_api.py   (rewrites include/mxtpu_ops.hpp)
"""
import keyword
import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

CPP_KEYWORDS = {
    "alignas", "alignof", "and", "asm", "auto", "bool", "break", "case",
    "catch", "char", "class", "const", "constexpr", "continue", "default",
    "delete", "do", "double", "else", "enum", "explicit", "export", "extern",
    "false", "float", "for", "friend", "goto", "if", "inline", "int", "long",
    "mutable", "namespace", "new", "noexcept", "not", "nullptr", "operator",
    "or", "private", "protected", "public", "register", "return", "short",
    "signed", "sizeof", "static", "struct", "switch", "template", "this",
    "throw", "true", "try", "typedef", "typeid", "typename", "union",
    "unsigned", "using", "virtual", "void", "volatile", "while", "xor",
}

IDENT = re.compile(r"^[A-Za-z_][A-Za-z0-9_]*$")


def cpp_ident(name):
    if not IDENT.match(name):
        return None
    if name in CPP_KEYWORDS or keyword.iskeyword(name):
        return name + "_"
    return name


def fmt_default(v):
    return "None" if v is None else repr(v)


def param_name(inp):
    """C++-safe parameter name for an op input."""
    pn = cpp_ident(inp)
    return pn if pn is not None else "in_" + re.sub(r"\W", "_", inp)


def gen_op(name, opdef):
    cname = cpp_ident(name)
    if cname is None:
        return None
    multi = callable(opdef.num_outputs) or opdef.num_outputs != 1
    ret = "std::vector<NDArray>" if multi else "NDArray"
    params, doc_attrs = [], []
    if opdef.variadic:
        params.append("const std::vector<NDArray>& data")
    else:
        for inp in opdef.inputs:
            pn = param_name(inp)
            if inp in opdef.optional:
                params.append(f"const NDArray& {pn} = NDArray()")
            else:
                params.append(f"const NDArray& {pn}")
    attr_names = []
    for an, dv in opdef.attrs.items():
        pn = cpp_ident(an)
        if pn is None:
            return None  # un-representable attr name: skip whole op
        attr_names.append((an, pn))
        params.append(f"const Attr& {pn} = Attr()")
        doc_attrs.append(f"{an}={fmt_default(dv)}")
    lines = []
    doc = f"// {name}"
    if doc_attrs:
        doc += " — attrs: " + ", ".join(doc_attrs)
    lines.append(doc)
    sig = f"inline {ret} {cname}(" + ", ".join(params) + ") {"
    lines.append(sig)
    lines.append("  detail::AttrWriter w_;")
    for an, pn in attr_names:
        lines.append(f'  w_.add("{an}", {pn});')
    if opdef.variadic:
        lines.append("  std::vector<void*> ins_;")
        lines.append("  ins_.reserve(data.size());")
        lines.append("  for (const auto& a_ : data) ins_.push_back(a_.handle());")
        call_args = (f'"{name}", ins_.data(), '
                     "static_cast<int>(ins_.size()), w_.str()")
    else:
        handles = ", ".join(f"{param_name(i)}.handle()" for i in opdef.inputs)
        if opdef.inputs:
            lines.append(f"  void* ins_[] = {{{handles}}};")
            call_args = f'"{name}", ins_, {len(opdef.inputs)}, w_.str()'
        else:
            call_args = f'"{name}", nullptr, 0, w_.str()'
    fn = "detail::invoke" if multi else "detail::invoke1"
    lines.append(f"  return {fn}({call_args});")
    lines.append("}")
    return "\n".join(lines)


def main():
    # the registry imports jax; keep it on the CPU backend
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from incubator_mxnet_tpu.ops.registry import OP_REGISTRY

    blocks, skipped = [], []
    for name in sorted(OP_REGISTRY):
        opdef = OP_REGISTRY[name]
        if opdef.name != name:
            continue  # alias: generate the primary only
        block = gen_op(name, opdef)
        if block is None:
            skipped.append(name)
            continue
        blocks.append(block)

    out = os.path.join(REPO, "include", "mxtpu_ops.hpp")
    with open(out, "w") as f:
        f.write(
            "// GENERATED by tools/gen_cpp_api.py — DO NOT EDIT.\n"
            "// C++ op-level API over the embedded imperative runtime\n"
            "// (reference role: cpp-package/include/mxnet-cpp/op.h).\n"
            f"// {len(blocks)} ops"
            + (f"; skipped (non-identifier names): {', '.join(skipped)}"
               if skipped else "")
            + "\n"
            "#pragma once\n\n"
            "#include \"mxtpu_imperative.hpp\"\n\n"
            "namespace mxtpu {\nnamespace ops {\n\n")
        f.write("\n\n".join(blocks))
        f.write("\n\n}  // namespace ops\n}  // namespace mxtpu\n")
    print(f"wrote {out}: {len(blocks)} ops, {len(skipped)} skipped")


if __name__ == "__main__":
    main()
