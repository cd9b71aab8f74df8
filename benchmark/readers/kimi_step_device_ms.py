"""Device time of one fit step of ``kimi_linear_fit_8k``: the reading of
``fit_step_device_ms`` (the union of the intervals in which an op ran on the
chip over the traced slice, a step) under the ``fit_kimi_linear`` driver's
name."""
from .fit_step_device_ms import read  # noqa: F401
