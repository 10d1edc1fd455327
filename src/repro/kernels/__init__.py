"""Pallas TPU kernels for the framework's compute hot spots.

Each subpackage ships the kernel (``pl.pallas_call`` + BlockSpec VMEM
tiling), a jitted wrapper (``ops.py``) and a pure-jnp oracle
(``ref.py``).  On the CPU backend kernels run with ``interpret=True``;
on TPU the same calls compile natively.  :func:`interpret_mode` is the
one place that decides which.
"""


def interpret_mode() -> bool:
    """Whether Pallas kernels run in interpret mode on the default backend.

    ``True`` on the CPU (the kernel body is traced into XLA), ``False``
    on a TPU (native Mosaic compile).  Any other backend raises: these
    kernels are written for Mosaic, and interpreting them there would
    hide that they never compiled.
    """
    import jax

    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(
        f"no Pallas path for backend {backend!r}: the kernels compile for "
        "TPU and run in interpret mode on the CPU only"
    )
