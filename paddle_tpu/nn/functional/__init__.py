"""paddle.nn.functional parity surface."""
from .activation import *  # noqa: F401,F403
from .common import *  # noqa: F401,F403
from .conv import *  # noqa: F401,F403
from .pooling import *  # noqa: F401,F403
from .norm import *  # noqa: F401,F403
from .loss import *  # noqa: F401,F403
from .attention import (  # noqa: F401
    enable_flash_attention,
    fused_ln_linear,
    fused_qkv_attention,
    rotary_embedding,
    scaled_dot_product_attention,
)
from ...ops.manipulation import pad  # noqa: F401
from ...ops.creation import one_hot  # noqa: F401
from .extra import *  # noqa: F401,F403
# vision/sequence functionals whose kernels live in ops.extended
from ...ops.extended import (affine_grid, diag_embed,  # noqa: F401
                             gather_tree, grid_sample, max_unpool2d,
                             temporal_shift)
