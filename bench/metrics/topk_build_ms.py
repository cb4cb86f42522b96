"""Device milliseconds per solve in the fused top-k similarity build
(the ``topk_similarity_fused`` program, ``kernels/topk_build_fused.py``),
from the trace. The preference estimate, layout and finalize around it
are ``topk_rest_ms``."""
from lib.trace import module_seconds

BUILD_MODULE = "jit_topk_similarity_fused"


def read(run, trace):
    if trace is None or run.data.get("solves", 0) < 1:
        return None
    t = module_seconds(trace, BUILD_MODULE)
    return t / run.data["solves"] * 1e3 if t > 0 else None
