from . import language  # noqa: F401
from . import vision  # noqa: F401
from .vision import get_model  # noqa: F401
