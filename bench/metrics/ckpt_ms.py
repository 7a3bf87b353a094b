"""Checkpoint stall per tree selection: over the levels that checkpoint,
the sum of the `checkpoint` event's time minus the `dispatch` event's
before it (`SelectionSupervisor.events`)."""


def read(r):
    per = []
    for events in r.events:
        stall, last = 0.0, None
        for ev in events:
            if ev["kind"] == "dispatch":
                last = ev["time"]
            elif ev["kind"] == "checkpoint" and last is not None:
                stall += ev["time"] - last
                last = None
        per.append(stall)
    return 1e3 * sum(per) / len(per) if per else None
