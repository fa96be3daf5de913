"""mxnet_tpu — a TPU-native deep learning framework with the capability
surface of Apache MXNet 1.3.1 (reference mounted at /root/reference).

Compute lowers to XLA (jit-cached eager ops, whole-graph compiled
executors); data parallelism is in-graph collectives over a device mesh;
the flash-attention kernels are Pallas.  See SURVEY.md for the full blueprint.
"""

__version__ = "0.1.0"

import os as _os
import time as _time

_import_started = _time.perf_counter()   # span mx.import, closed below

# Server-role bootstrap: a process launched with DMLC_ROLE=server never
# returns to user code — the reference's behavior
# (python/mxnet/kvstore_server.py _init_kvstore_server_module, invoked
# from python/mxnet/__init__.py).  Implementation detail: we re-exec a
# fresh interpreter running ``-m mxnet_tpu.kvstore_server`` instead of
# blocking here, because a server loop inside this (still-initializing)
# package import would deadlock its handler threads on the package
# import lock the moment they unpickle an optimizer.
#
# This block sits at the TOP of the package, before any heavy imports:
# the pre-exec interpreter used to pay the FULL package import (jax,
# gluon, module, ...) only to throw it away in execv and import it all
# again — doubling server spin-up, which the multi-process dist drills
# pay per spawned server.
if _os.environ.get("DMLC_ROLE") == "server" and \
        not _os.environ.get("_MXTPU_SERVER_BOOT"):
    import sys as _sys
    # A ``python -m mxnet_tpu.kvstore_server ...`` launch imports this
    # package while argv[0] is still the "-m" placeholder; let it
    # proceed so its own argv (kv type) is honored rather than
    # re-execing over it.
    if _sys.argv and _sys.argv[0] != "-m":
        _os.environ["_MXTPU_SERVER_BOOT"] = "1"
        _pkg_parent = _os.path.dirname(_os.path.dirname(
            _os.path.abspath(__file__)))
        _pp = _os.environ.get("PYTHONPATH", "")
        _os.environ["PYTHONPATH"] = _pkg_parent + (_os.pathsep + _pp
                                                   if _pp else "")
        _os.execv(_sys.executable,
                  [_sys.executable, "-m", "mxnet_tpu.kvstore_server",
                   _os.environ.get("MXNET_KVSTORE_TYPE", "dist_sync")])

from .base import MXNetError  # noqa: F401
from .context import Context, cpu, gpu, tpu, current_context, num_gpus, \
    num_tpus  # noqa: F401
from . import ndarray  # noqa: F401
from . import ndarray as nd  # noqa: F401
from . import symbol  # noqa: F401
from .symbol import AttrScope  # noqa: F401
from . import attribute  # noqa: F401
from . import name  # noqa: F401
from . import log  # noqa: F401
from . import symbol as sym  # noqa: F401
from .executor import Executor  # noqa: F401
from . import random  # noqa: F401
from . import autograd  # noqa: F401
from . import initializer  # noqa: F401
from .initializer import init  # noqa: F401
from . import optimizer  # noqa: F401
from . import lr_scheduler  # noqa: F401
from . import metric  # noqa: F401
from . import gluon  # noqa: F401
from . import io  # noqa: F401
from . import image  # noqa: F401
from . import module  # noqa: F401
from . import module as mod  # noqa: F401  (reference alias: mx.mod)
from . import model  # noqa: F401
from . import callback  # noqa: F401
from . import profiler  # noqa: F401
from . import monitor  # noqa: F401
from . import operator  # noqa: F401
from .monitor import Monitor  # noqa: F401
from .module import Module  # noqa: F401
from . import kvstore  # noqa: F401
from . import kvstore as kv  # noqa: F401
from . import rnn  # noqa: F401
from . import contrib  # noqa: F401
from . import parallel  # noqa: F401
from . import recordio  # noqa: F401
from . import visualization  # noqa: F401
viz = visualization  # reference alias: mx.viz
from . import subgraph  # noqa: F401
from . import resilience  # noqa: F401
from . import config  # noqa: F401
from . import sanitizer  # noqa: F401  (graftsan bridge — see MXNET_SAN)
from . import serve  # noqa: F401  (compiled inference subsystem)
from . import quantize  # noqa: F401  (serving-path int8 pipeline)
from . import rtc  # noqa: F401
from .runtime import engine  # noqa: F401

# Persistent XLA compilation cache, applied at import so EVERY compile
# in the process — fused train steps, AOT serve buckets, dist-drill
# child processes — can hit it: at JAX_COMPILATION_CACHE_DIR where set,
# else the fixed in-checkout default.  Does not initialize a backend.
config.enable_compile_cache()


def waitall():
    engine.wait_all()


profiler._store("mx.import", "setup", _import_started,
                _time.perf_counter())
