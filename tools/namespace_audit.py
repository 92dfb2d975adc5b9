"""Audit every reference __all__ list against the live paddle_tpu surface.

Usage: JAX_PLATFORMS=cpu python tools/namespace_audit.py

Walks /root/reference/python/paddle for files with __all__, resolves the
same module path on paddle_tpu, and reports missing names / modules.
Known-excluded subsystems (SURVEY A.7) are filtered to keep the report
actionable.
"""
import os
import re
import sys

REF = "/root/reference/python/paddle"

EXCLUDED_PREFIXES = (
    "cinn", "tensorrt", "device.xpu", "incubate.xpu",
    "distributed.ps", "autograd.ir_backward", "cost_model",
    "incubate.distributed.fleet.fleet_util",
    # the package re-export shadows the module attribute in the REFERENCE
    # too (paddle.text.viterbi_decode is the function there as well, so
    # this attribute walk fails identically on the reference); the module
    # file exists with matching __all__ at paddle_tpu/text/viterbi_decode.py
    "text.viterbi_decode",
)


def ref_all(path):
    src = open(path, errors="ignore").read()
    i = src.find("__all__")
    if i < 0:
        return []
    j = src.find("]", i)
    return re.findall(r"['\"]([A-Za-z0-9_]+)['\"]", src[i:j])


def main():
    import paddle_tpu as paddle
    mods = []
    for root, dirs, files in os.walk(REF):
        dirs[:] = [d for d in dirs
                   if d not in ("tests", "__pycache__", "libs", "include")]
        for f in files:
            p = os.path.join(root, f)
            if f == "__init__.py" or (
                    f.endswith(".py")
                    and "__all__" in open(p, errors="ignore").read()[:5000]):
                mods.append(p)
    report = []
    for path in mods:
        rel = os.path.relpath(path, REF)
        modpath = rel[:-3].replace("/__init__", "").replace("/", ".")
        if modpath in ("", "__init__"):
            continue
        if any(modpath.startswith(e) for e in EXCLUDED_PREFIXES):
            continue
        names = ref_all(path)
        if not names:
            continue
        obj = paddle
        ok = True
        for part in modpath.split("."):
            if not hasattr(obj, part):
                ok = False
                break
            obj = getattr(obj, part)
        if not ok:
            report.append(f"{modpath}: MODULE MISSING ({len(names)} names)")
            continue
        missing = [n for n in dict.fromkeys(names) if not hasattr(obj, n)]
        if missing:
            report.append(f"{modpath}: missing {missing}")
    for line in sorted(report):
        print(line)
    print(f"\n{len(report)} modules with gaps (excluded: "
          f"{', '.join(EXCLUDED_PREFIXES)})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
