from . import (classification, detection, language, metric, pose,
               segmentation, ssl, stereo)  # noqa: F401
