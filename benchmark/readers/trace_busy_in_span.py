"""Device time against the program's own spans, on the trace's one clock.
``params``: ``span`` (the program's span name, read from its
``mosaic.<span>`` annotation), ``q``, ``scale`` (to the metric's unit from
seconds), ``measure``:

- ``busy`` (default): the first device's busy time (union of op
  intervals) inside each annotation's interval;
- ``first_op_delay``: from each annotation's start to the start of the
  next module run on each device, the latest device counting (the launch
  skew across chips); the per-chip medians are printed as ``[bench]
  launch_to_device:``. Only for delays well over a millisecond: in this
  libtpu's trace the device planes' clock leads the host plane's by
  0.1-1 ms, so one chip's launch-to-start (under that) reads as noise
  around zero, and the metric is listed for the four-chip cell alone."""

import bisect


def read(ctx, params):
    from benchmark.harness.stats import percentile

    tr_mod = ctx.spec.module("readers", "_trace")
    tr = tr_mod.of_run(ctx)
    if tr is None:
        return None
    spans = [(s, e) for n, s, e, _t in tr["program"] if n == params["span"]]
    if not spans:
        return None
    vals = []
    if params.get("measure", "busy") == "busy":
        merged = tr_mod.busy_intervals(tr_mod.first_device(tr)["ops"])
        starts = [m[0] for m in merged]
        vals = [tr_mod.busy_inside(merged, starts, s, e) for s, e in spans]
    else:
        runs = {
            name: [m[1] for m in dev["modules"]]
            for name, dev in tr["devices"].items()
        }
        per_chip: dict = {name: [] for name in runs}
        for s, _e in spans:
            delays = {}
            for name, starts in runs.items():
                i = bisect.bisect_left(starts, s)
                if i < len(starts):
                    delays[name] = starts[i] - s
            if len(delays) == len(runs) and delays:
                vals.append(max(delays.values()))
                for name, d in delays.items():
                    per_chip[name].append(d)
        if vals:
            ctx.say("launch_to_device", **{
                name.rsplit(":", 1)[-1]: round(percentile(v, 0.5) / 1e6, 4)
                for name, v in sorted(per_chip.items())
            }, unit="ms", dispatches=len(vals))
    if not vals:
        return None
    return percentile(vals, float(params["q"])) / 1e9 * float(params["scale"])
