"""paddle_tpu.nn.functional — functional NN ops.

Parity: python/paddle/nn/functional/ (activation, common, conv, pooling, norm,
loss, flash_attention modules)."""

from .activation import *  # noqa: F401,F403
from .attention import *  # noqa: F401,F403
from .common import *  # noqa: F401,F403
from .conv import *  # noqa: F401,F403
from .loss import *  # noqa: F401,F403
from .norm import *  # noqa: F401,F403
from .pooling import *  # noqa: F401,F403
from .ssm import *  # noqa: F401,F403
from .extras import *  # noqa: F401,F403
from ..layer.rnn import birnn, rnn  # noqa: F401  (functional recurrence entry points)
from ...ops.pallas.flash_attention import flash_attn_unpadded  # noqa: F401
from ...ops.manipulation import diag_embed  # noqa: F401
