"""A model family for tests only: the dense family, counting the calls
into each of its entry points. A configuration that names this file
shows that the harness takes a family as an added file."""
from collections import Counter
from functools import wraps

from perfbench.reference import dense

CALLS = Counter()


def _counted(name):
    fn = getattr(dense, name)

    @wraps(fn)
    def call(*args, **kwargs):
        CALLS[name] += 1
        return fn(*args, **kwargs)
    return call


program_config = _counted("program_config")
init_weights = _counted("init_weights")
forward_hidden = _counted("forward_hidden")
matmul_params = _counted("matmul_params")
request_flops = _counted("request_flops")
