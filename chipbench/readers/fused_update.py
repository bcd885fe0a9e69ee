"""Reader of the fused optimizer update's time from the device trace."""

import re

_FIRST_RESULT = re.compile(r"^%[\w.\-]+ = \(?([a-z]+\d*\[[\d,]*\])")
_OPERAND = re.compile(r"%[\w.\-]+")


def _elements(array_type: str) -> int:
    count = 1
    for dim in array_type[array_type.index("[") + 1:-1].split(","):
        count *= int(dim or 1)
    return count


def fused_update_roofline(context, kernel, bytes_per_element):
    """The fused update's share of its HBM roofline, in percent.

    Least time: for every device event whose name carries the kernel
    identity ``kernel`` (a regular expression over the trace's operation
    names: the ``kernel_metadata`` the program gives ``pallas_call``), the
    elements of the event's first result (the new parameters) times
    ``bytes_per_element`` over the HBM peak. The bytes per element are the
    optimiser's arithmetic at the configuration's precision (AdaGrad under
    ``bf16_mixed``: the bf16 gradient read, the float32 parameter and
    accumulator read and written, 2 + 4 x 4 = 18), not the kernel's operand
    list, so the yardstick does not move with the implementation.

    Measured time: the kernel events' seconds AND those of the events that
    hand them their operands (named in the kernel event's own operand list)
    and that take their results (an operand of a kernel event's result type
    named ``%pallas_call...``, or ``%custom-call...`` where XLA passes the
    result through a bitcast of its own). On the v5e XLA stages a kernel's
    operands into VMEM (memory space ``S(1)``) and relayouts the leaf to and
    from the kernel's ``(rows, 128)`` view in events of its own; the kernel
    event alone then leaves out the side of the work that crosses HBM and
    reads far above 100%."""
    trace, peaks = context["trace"], context["peaks"]
    ident = re.compile(kernel)
    by_lhs = {name.split(" = ", 1)[0]: name for name in trace.op_seconds}
    kernels, result_types, feeders = [], set(), set()
    elements = 0.0
    for name, count in trace.op_counts.items():
        head = _FIRST_RESULT.match(name)
        if not head or not ident.search(name):
            continue
        kernels.append(name)
        result_types.add(head.group(1))
        elements += _elements(head.group(1)) * count
        operands = name.split("custom-call(", 1)[-1].split(
            "custom_call_target", 1)[0]
        feeders.update(by_lhs[tok] for tok in _OPERAND.findall(operands)
                       if tok in by_lhs)
    if not kernels:
        return None
    taken = re.compile(
        "(" + "|".join(re.escape(t) for t in result_types)
        + r")(\{[^}]*\})? %(pallas_call|custom-call)[\w.\-]*[,)]")
    takers = {name for name in trace.op_seconds
              if taken.search(name[name.find("(", name.find(" = ")):])}
    measured = sum(trace.op_seconds[n]
                   for n in {*kernels, *feeders, *takers})
    if measured <= 0:
        return None
    least = elements * bytes_per_element / peaks["hbm_bytes_per_s"]
    return 100.0 * least / measured
