"""Share (%) of a peak that the traced span's device-busy time reached: the
bytes (or operations) the span's requests need, by a function under
`rooflines/`, over the peak, over the busy seconds. The span's requests are
those answered inside it by the load generator's clock. args: function (a
module under rooflines/ with `request_bytes(shape, splits)`), peak (a key
of the peaks table)."""


def read(run, function: str, peak: str):
    if not run.trace or not run.trace["busy_s"]:
        return None
    lo, hi = run.trace_span
    reckon = run.load_module("rooflines", function).request_bytes
    per_shape = {name: reckon(shape, run.splits)
                 for name, shape in run.shapes.items()}
    needed = sum(per_shape[r["shape"]] for r in run.records
                 if r["ok"] and lo <= r["t_done"] <= hi)
    if not needed:
        return None
    least_s = needed / run.peak(peak)
    return 100.0 * least_s / (run.trace["busy_s"] * run.trace["chips"])
