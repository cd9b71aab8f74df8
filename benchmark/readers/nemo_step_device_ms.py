"""Device time of one fit step of ``nemotron3_super_fit_8k``: the reading of
``fit_step_device_ms`` (the union of the intervals in which an op ran on the
chip over the traced slice, a step) under the ``fit_nemotron_h`` driver's
name."""
from .fit_step_device_ms import read  # noqa: F401
