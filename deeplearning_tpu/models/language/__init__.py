from . import glm_moe_lite, mellum  # noqa: F401
