#!/usr/bin/env python
"""Environment diagnosis report (reference: tools/diagnose.py — the
"attach this to your bug report" dump: platform, python, deps, build
info, connectivity).  Offline build: no network checks; instead reports
the pieces that matter here — jax/XLA backends, device inventory,
native library builds, and key env knobs.

    python tools/diagnose.py
"""

from __future__ import annotations

import os
import platform
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def section(title):
    print("----------%s----------" % title)


def main():
    section("Platform")
    print("system   :", platform.platform())
    print("machine  :", platform.machine())
    print("processor:", platform.processor() or "n/a")
    print("cpus     :", os.cpu_count())

    section("Python")
    print("version :", sys.version.replace("\n", " "))
    print("prefix  :", sys.prefix)

    section("Dependencies")
    for mod in ("numpy", "jax", "jaxlib", "cv2", "google.protobuf"):
        try:
            m = __import__(mod)
            ver = getattr(m, "__version__", "unknown")
            print("%-16s %s" % (mod, ver))
        except ImportError as e:
            print("%-16s MISSING (%s)" % (mod, e))

    section("Framework")
    try:
        import mxnet_tpu as mx
        from mxnet_tpu.ops.registry import list_ops
        print("mxnet_tpu:", os.path.dirname(mx.__file__))
        print("operators:", len(list_ops()))
    except Exception as e:
        print("import failed:", e)

    section("Devices")
    print("JAX_PLATFORMS:", os.environ.get("JAX_PLATFORMS", "<unset>"))
    try:
        import jax
        print("default backend:", jax.default_backend())
        for d in jax.devices():
            print("  ", d, getattr(d, "device_kind", ""))
    except Exception as e:
        # e.g. another process holds the chip: report, keep diagnosing
        print("device discovery failed:", e)

    section("Native builds")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for lib in ("libmxtpu_predict.so", "libmxtpu_nd.so",
                "librecordio_reader.so"):
        path = os.path.join(root, "build", lib)
        print("%-22s %s" % (lib, "built" if os.path.exists(path)
                            else "not built (make -C src/capi src/io)"))

    section("Environment knobs")
    try:
        from mxnet_tpu import config
        for name in config.list_env():
            print("%-40s %r" % (name, config.get_env(name)))
    except Exception:
        for k, v in sorted(os.environ.items()):
            if k.startswith(("MXNET_", "DMLC_", "XLA_", "JAX_")):
                print("%-40s %r" % (k, v))
    return 0


if __name__ == "__main__":
    sys.exit(main())
