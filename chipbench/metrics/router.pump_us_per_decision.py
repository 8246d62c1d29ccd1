"""Router host time a decision, us: the summed ``router.pump`` spans of the window over the frames answered."""


def read(rec):
    spans = getattr(rec, "program_spans", None)
    answered = getattr(rec, "answered", 0)
    if not spans or not answered:
        return None
    pump = sum(t1 - t0 for name, t0, t1 in spans if name == "router.pump")
    return 1e6 * pump / answered if pump > 0 else None
