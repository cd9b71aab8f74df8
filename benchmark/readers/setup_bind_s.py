"""The program's bind and initialisation before the window: the sums of
``perf.setup.bind``, ``perf.setup.init_params`` and
``perf.setup.init_optimizer`` (``BaseModule.fit``)."""
from . import setup_snapshot


def read(slice_):
    return setup_snapshot.histogram_sums(
        slice_, 'perf.setup.bind', 'perf.setup.init_params',
        'perf.setup.init_optimizer')
