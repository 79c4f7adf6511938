from .env import auto_axes  # noqa: F401
