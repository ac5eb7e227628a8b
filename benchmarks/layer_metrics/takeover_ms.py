"""From the first ``gp.fo.elect_start`` to the end of the last
``gp.fo.install``, over the survivors: how long the batched elections for
the victim's groups took once a survivor suspected it.  Nothing where the
traced seconds hold no election or no install."""
from benchmarks import span_ring


def read(run: dict):
    spans = span_ring.session() or []
    starts = span_ring.of(spans, "fo.elect_start")
    installs = span_ring.of(spans, "fo.install")
    if not starts or not installs:
        return None
    return 1e3 * (max(s["t1"] for s in installs)
                  - min(s["t0"] for s in starts))
