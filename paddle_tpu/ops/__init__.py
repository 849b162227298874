"""Op lowering registry population.

Importing this package registers every op's JAX lowering rule (the analog of
the reference's static-initializer REGISTER_OPERATOR/REGISTER_OP_*_KERNEL
sites, op_registry.h).
"""

from ..core import registry
from . import common  # noqa: F401
from . import math_ops  # noqa: F401
from . import tensor_ops  # noqa: F401
from . import nn_ops  # noqa: F401
from . import moe_ops  # noqa: F401
from . import kda_ops  # noqa: F401
from . import mamba2_ops  # noqa: F401
from . import optimizer_ops  # noqa: F401
from . import loss_ops  # noqa: F401
from . import image_ops  # noqa: F401
from . import crf_ops  # noqa: F401
from . import ctc_ops  # noqa: F401
from . import search_ops  # noqa: F401
from . import quant_ops  # noqa: F401
from . import metric_ops  # noqa: F401
from . import detection_ops  # noqa: F401
from . import dist_ops  # noqa: F401
from . import collective_ops  # noqa: F401
from . import misc_ops  # noqa: F401
from . import control_ops  # noqa: F401
from . import compat_ops  # noqa: F401
from . import pallas_kernels  # noqa: F401

get_op = registry.get_op
is_registered = registry.is_registered
register = registry.register
