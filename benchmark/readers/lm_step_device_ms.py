"""Device time of one fit step of a language-model cell: the reading of
``fit_step_device_ms`` (the union of the intervals in which an op ran on the
chip over the traced slice, a step) under the ``fit_lm`` driver's name."""
from .fit_step_device_ms import read  # noqa: F401
