"""Roofline share of the planned kernel on the LM head's three training
products: the forward ``[T, d] x [d, V]`` (computed transposed, its result
``[V, T]``), the input gradient ``[T, V] x [V, d]`` and the weight gradient
``[d, T] x [T, V]``, with ``T`` the tokens of one chip's share of the
step (the step's tokens over the chips: on several chips each runs the
kernel on its own rows against the whole head).  Each kernel event of every
chip in the traced window is priced at its own ``(m, k, n)``: its 2-D result shape
names two of ``T``, ``d`` and ``V`` (a dimension padded up to a multiple of
128 counts at its own size) and the third is contracted.  Its least time is
the larger of its operations (on the head's nonzero blocks) over the
chip's peak and its bytes (each operand read once, the result written once,
bf16) over the bandwidth; the share is the sum of least times over the sum
of the events' device time.  At 4096 tokens (mamba2, one chip) every
product is bound by compute."""
from harness import flops, kernels


def _size(dim: int, sizes: dict) -> str:
    for name, size in sizes.items():
        if dim in (size, -(-size // 128) * 128):
            return name
    raise ValueError(f"a head kernel result dimension {dim} is none of {sizes}")


def read(run):
    if run.kind != "train" or run.trace is None:
        return None
    events = run.trace.kernel_events(run.kernel_pattern)
    if not events:
        return None
    sizes = {"T": run.head["m"] // run.chips, "d": run.head["k"], "V": run.head["n"]}
    least = 0.0
    for e in events:
        out = [_size(x, sizes) for x in kernels.result_shape(e)]
        (inner,) = set(sizes) - set(out)
        m, k, n = sizes[out[0]], sizes[inner], sizes[out[1]]
        ops = 2.0 * m * k * n * run.head["density"]
        byts = flops.BF16 * (m * k + k * n + m * n)
        least += flops.least_time(ops, byts, run.peak)[0]
    busy = sum(e.dur for e in events)
    return 100.0 * least / busy if busy > 0 else None
