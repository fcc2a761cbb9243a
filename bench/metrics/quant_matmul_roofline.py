"""Share of its roofline the Pallas int8 matmul reached in the traced
sub-window: least time from its operations and bytes (from shapes) over
the device time of its events."""


def match(name: str) -> bool:
    """The kernel is a ``tpu_custom_call`` whose weight operand is int8
    (the trace carries no kernel name)."""
    return "custom-call(" in name and " s8[" in name


def read(v):
    return v.kernel_share("quant_matmul", match)
