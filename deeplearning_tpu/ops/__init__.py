from . import anchors, boxes, losses, matcher, nms, roi_align  # noqa: F401
from . import window_utils  # noqa: F401
from .padding import torch_pad  # noqa: F401
