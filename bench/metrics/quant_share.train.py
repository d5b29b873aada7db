"""Share of the traced training step's device self time spent on
quantization in XLA: ops under the FQT seam's ``q[path|role]`` or
``qk[path]`` markers, the GEMM kernels excluded (``bench/attribution.py``)."""

from bench import attribution


def read(run):
    return attribution.share(run, "quant")
