from repro.kernels.qgemm.ops import qgemm  # noqa: F401
