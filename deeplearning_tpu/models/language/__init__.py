from . import glm_moe_lite  # noqa: F401
