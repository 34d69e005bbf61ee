"""Pure-JAX operator library (the ``src/operator/`` counterpart).

Importing this package registers all ops into ``registry.OPS``; the
``mx.nd`` namespace is generated from that registry.
"""
from . import tensor  # noqa: F401
from . import nn  # noqa: F401
from . import ssm  # noqa: F401
from . import attention  # noqa: F401
from . import detection  # noqa: F401
from . import quantization  # noqa: F401
from . import vision  # noqa: F401
from . import control_flow  # noqa: F401
from . import optimizer_ops  # noqa: F401
from . import subgraph_ops  # noqa: F401
from .registry import OPS, OpDef, register_op, alias_op  # noqa: F401
