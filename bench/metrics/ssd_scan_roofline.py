"""Share of its roofline the Pallas SSD scan reached in the traced
sub-window (mamba prefill)."""


def match(name: str) -> bool:
    """The scan is the ``tpu_custom_call`` that returns a tuple (the output
    and the final state); the trace carries no kernel name."""
    head, _, rhs = name.partition(" = ")
    return ("custom-call(" in rhs and rhs.startswith("(")
            and "AllocateBuffer" not in rhs)


def read(v):
    return v.kernel_share("ssd_scan", match)
