"""Device milliseconds per solve outside both the fused top-k build and
the sparse sweep program: the preference estimate, the layout work and
the finalize around them (every window op in neither
``topk_similarity_fused`` nor ``run_topk``), from the trace."""
MAIN_MODULES = ("jit_topk_similarity_fused", "jit_run_topk")


def read(run, trace):
    if trace is None or run.data.get("solves", 0) < 1:
        return None
    t = sum(s for m, s in trace["module_s"].items()
            if not any(name in m for name in MAIN_MODULES))
    return t / run.data["solves"] * 1e3 if t > 0 else None
