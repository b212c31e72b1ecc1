"""The device time of the operations under the ``E`` layers' named
scopes (``latent_down``, ``latent_experts``, ``latent_up``,
``shared_expert``: the router, the dispatch, the two grouped products
and the squared relu between them, the two latent projections, the
shared expert) as a share of the decode programs' (``device_decode``)
device time inside the traced window, in percent: what says the
mechanism the configuration adds does the work the cell was sized for
(five layers of eleven read their touched experts, by the shapes ~half
of a step's bytes).  Which operations of the compiled program carry a
scope is the driver's to say (``scope_ops``: it reads the scopes out of
the compiled decode program's own text, an operation's ``op_name``);
this reader adds up their events.  A program without those scopes gives
none and the metric is left out."""

LAYER = "step program (kernels and fusions)"
SOURCE = "device_trace"
MOVES = "tokens_per_s"
SCOPES = ("latent_down", "latent_experts", "latent_up", "shared_expert")


def read(run):
    from chipbench.trace import op_name
    t, c = run.trace, run.counters
    scoped = c.get("scope_ops") or {}
    names = {n for scope in SCOPES for n in scoped.get(scope, ())}
    if t is None or not names:
        return None
    lo, hi = t.window
    programs = [(s, e) for name, s, e in t.devices[0].modules
                if "device_decode" in name and s >= lo and e <= hi]
    whole = sum(e - s for s, e in programs)
    if not whole:
        return None
    # the scoped operations' events inside those programs' runs (a run
    # cut by the window's end leaves its events out with it)
    inside = sum(e - s for name, s, e in t.devices[0].ops
                 if op_name(name) in names
                 and any(ps <= s and e <= pe for ps, pe in programs))
    return 100.0 * inside / whole if inside else None
