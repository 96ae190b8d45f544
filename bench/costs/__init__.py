"""Operations and bytes of each kernel's calls, one module per kernel.

A module ``<kernel>.py`` defines ``match(op)``, which takes an operation
parsed from the device trace (``xspace.parse_op``) and returns the sizes
of the call when it is one of this kernel's, else None, and
``cost(sizes)``, which returns ``(flops, bytes)``: the work and the HBM
traffic the call cannot do without, each operand read once and the
result written once.  A kernel's least time is the larger of flops over
the peak rate and bytes over the peak bandwidth.
"""
