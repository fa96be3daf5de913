"""Operator library.

Importing this package registers every built-in operator into the registry
(``registry.py``), which the ``nd``/``sym`` front ends then expose as
generated functions — the in-process equivalent of the reference's op
reflection at import (``python/mxnet/base.py:578`` ``_init_op_module``).
"""

from .registry import (Op, register_op, get_op, list_ops, invoke,  # noqa
                       alias)
from . import elemwise      # noqa: F401
from . import tensor        # noqa: F401
from . import reduce        # noqa: F401
from . import nn            # noqa: F401
from . import random_ops    # noqa: F401
from . import optimizer_ops  # noqa: F401
from . import rnn           # noqa: F401
from . import control_flow  # noqa: F401
from . import quantization  # noqa: F401
from . import image         # noqa: F401
from . import detection     # noqa: F401
from . import spatial       # noqa: F401
from . import attention     # noqa: F401
from . import lm_blocks     # noqa: F401
from . import delta_rule    # noqa: F401
from . import state_space   # noqa: F401
from . import parity        # noqa: F401  (must come last: aliases)
