from . import glm_moe_lite, granite, mellum  # noqa: F401
