#!/usr/bin/env python
"""Scrape the reference NNVM registry for user-callable op names.

Produces the pinned manifest `tests/data/ref_public_ops.txt` that
`tests/test_registry_manifest.py` diffs the live registry against, turning
"registry diff empty" from a claim in prose into a tested invariant.

Sources scraped (ref: src/operator/**/*.cc):
- `NNVM_REGISTER_OP(x)` registrations
- `MXNET_OPERATOR_REGISTER_*(x, ...)` macro invocations (these forward to
  NNVM_REGISTER_OP). The `_SAMPLING` family is skipped: it registers
  `_sample_<x>` (non-public) and adds its public spelling via add_alias,
  which the next rule captures.
- `.add_alias("x")` deprecated/public alternate spellings

A name is user-callable iff it does not start with `_` (the reference
frontend hides underscore-prefixed internals the same way,
ref: python/mxnet/ndarray/register.py).

Run: python tools/gen_ref_op_manifest.py [ref_root] > tests/data/ref_public_ops.txt
"""
import glob
import re
import sys

REF = sys.argv[1] if len(sys.argv) > 1 else "/root/reference"


def scrape(ref_root):
    names = set()
    for path in glob.glob(f"{ref_root}/src/operator/**/*.cc", recursive=True):
        with open(path, encoding="utf-8", errors="replace") as f:
            in_define = False
            for line in f:
                s = line.strip()
                if in_define or s.startswith("#") or "SAMPLING" in s:
                    # macro definitions (incl. backslash-continued bodies)
                    # and the _sample_-prefixed SAMPLING family
                    in_define = (in_define or s.startswith("#define")) \
                        and s.endswith("\\")
                    continue
                for m in re.finditer(r"NNVM_REGISTER_OP\((\w+)\)", s):
                    names.add(m.group(1))
                for m in re.finditer(
                        r"MXNET_REGISTER_OP_PROPERTY\((\w+)[,)]", s):
                    names.add(m.group(1))  # legacy OpProp era (svm_output.cc)
                for m in re.finditer(r"MXNET_OPERATOR_REGISTER\w*\((\w+)[,)]", s):
                    names.add(m.group(1))
                for m in re.finditer(r'\.add_alias\("([^"]+)"\)', s):
                    names.add(m.group(1))
    return sorted(n for n in names if not n.startswith("_"))


if __name__ == "__main__":
    for n in scrape(REF):
        print(n)
