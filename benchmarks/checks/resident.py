"""`resident`: the device mirror equals the host journal's truth.

What it adds to `answer`. `answer` solves the host's arrays
(`state.problem()`) again with the C++ solver and compares objectives: a
mirror that had drifted from the host would be caught there only if the
drift changed the closing round's optimum. Here the mirror itself is
read: the five arrays the service keeps on the chip (excess, src, dst,
cap, cost), fetched once the run has ended, after hundreds of rounds of
deltas scattered into them, and compared entry for entry with the host's
in the types the upload casts them to (limit: 0 entries differ). Equal
arrays and `answer`'s equal objectives together are the configuration's
sentence: the closing round, solved on the resident arrays, has native's
objective on `state.problem()`. A service that kept no mirror (the flag
did not take) is a fault.

Not a fault: a closing round whose arrays or plan went up whole. Growth
past a power of two or a layout rebuild may fall on any round (at
rehearsal size the closing round's 100 pods are 40% of the backlog, and
the plan is rebuilt there); `facts["resident"]` says what the last
refresh was, and a traced run's `full_uploads` counts them in the window.
The plan tensors are not compared: whether the mirror's are behind the
host's is private to the program, and a wrong plan is a wrong objective,
which `answer` sees.
"""

from typing import List

import numpy as np


def check(ctx) -> List[str]:
    solver = ctx.svc.scheduler.solver
    mirror = getattr(solver, "resident", None)
    if mirror is None or mirror.d_cap is None:
        return ["the service keeps no arrays on the device (--device-resident did not take)"]
    problem = solver.state.problem()
    pairs = (
        ("excess", mirror.d_excess, problem.excess.astype(np.int32)),
        ("src", mirror.d_src, problem.src),
        ("dst", mirror.d_dst, problem.dst),
        ("cap", mirror.d_cap, problem.cap),
        ("cost", mirror.d_cost, problem.cost.astype(np.int32)),
    )
    faults = []
    compared = differ = 0
    for name, dev, host in pairs:
        got = np.asarray(dev)
        if got.shape != host.shape:
            faults.append(f"device mirror `{name}` has shape {got.shape}, the host's {host.shape}")
            continue
        bad = np.flatnonzero(got != host)
        compared += host.size
        differ += bad.size
        if bad.size:
            i = int(bad[0])
            faults.append(
                f"device mirror `{name}` differs from the host's in {bad.size} of {host.size} "
                f"entries (first: [{i}] device {int(got[i])}, host {int(host[i])})"
            )
    ctx.facts["resident"] = {
        "mirror_entries": compared, "differ": differ, "limit": 0,
        "refreshes": int(mirror.version), "closing_upload": mirror.last_upload_kind,
        "closing_plan": mirror.last_plan_kind,
    }
    return faults
