# OPTIONAL layer. Add <name>.py (or .cu) + ops.py + ref.py ONLY
# for compute hot-spots the paper itself optimizes with a custom
# kernel. Leave this package empty if the paper has none.
import jax


def default_interpret(interpret: bool | None = None) -> bool:
    """Resolve a kernel entry point's ``interpret`` argument.

    ``None`` compiles natively on TPU and emulates elsewhere (the CPU
    test harness), so the same call site is the correctness harness on
    CPU and the hot path on TPU. An explicit ``interpret=True`` on a TPU
    backend is refused: an emulated kernel there would report a result
    the chip never computed."""
    on_tpu = jax.default_backend() == "tpu"
    if interpret is None:
        return not on_tpu
    if interpret and on_tpu:
        raise ValueError("interpret=True on a TPU backend: Pallas kernels "
                         "run natively on the chip")
    return bool(interpret)
