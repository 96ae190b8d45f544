"""Operations and bytes of each kernel's calls, one module per kernel.

The device trace names a kernel's calls (``kernel_metadata``), and
``xspace.costed`` hands each to the module of that name, ``<kernel>.py``:
its ``match(op)`` takes the operation parsed from the trace
(``xspace.parse_op``) and reads the call's sizes from its operands, or
returns None where they are not the kernel's; ``cost(sizes)`` returns
``(flops, bytes)``: the work and the HBM traffic the call cannot do
without, each operand read once and the result written once.  A call
with no name (a trace recorded before the program named its kernels)
goes to the first module whose ``match`` accepts its operands.  A
kernel's least time is the larger of flops over the peak rate and bytes
over the peak bandwidth.
"""
