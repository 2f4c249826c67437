"""Device seconds under the ``kernel.spmm`` scope (every plan node's
SpMM, whatever its backend) in the traced window, over the colorings
answered in it."""

from bench.harness.readers import kernel_s_per_coloring


def read(run):
    return kernel_s_per_coloring(run, "kernel.spmm")
