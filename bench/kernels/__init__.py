"""Kernel cost files, one per Pallas kernel, named as the jitted function
that wraps its ``pallas_call`` (the name a trace gives its calls; see
``bench/trace.py``). Each has ``cost(operands, c, family) -> (FLOPs, least
bytes)`` of one call, from its operands' (dtype, shape) pairs with the
kernel's zero padding taken off; ``family`` is the configuration's family
module. ``bench/run.py`` reads the roofline share of every kernel that has a
file here and calls in the trace."""
