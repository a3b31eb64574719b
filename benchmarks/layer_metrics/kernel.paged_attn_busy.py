"""Share of the device's busy time spent in the paged-attention Pallas
kernel, in percent: the trace's operations whose name carries the kernel's
name, over the union of all operation intervals. Layer: kernels. Moves
tokens_per_s."""

# The kernel body in tpu_engine/ops/paged_attention.py is `_paged_kernel`;
# Mosaic names the custom call after it.
PATTERN = "paged"


def compute(run):
    trace = run["trace"]
    if not trace or not trace["busy_s"]:
        return None
    seconds = sum(s for name, s in trace["op_seconds"].items()
                  if PATTERN in name.lower())
    return 100.0 * seconds / trace["busy_s"]
