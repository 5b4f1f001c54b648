"""`binding`: every pod that arrives gets exactly one Binding.

As far as a run can show it: every pod due in the window got a Binding
(within the drain), and no pod of the whole run got a second one. A
deployment that may bind a pod again (after an eviction, say) states
another guarantee under another name, and brings that module.
"""

from typing import List


def check(ctx) -> List[str]:
    faults = []
    missing = [p for p in ctx.due if p not in ctx.bind_stamps]
    if missing:
        faults.append(f"{len(missing)} pods due in the window got no Binding (first: {missing[0]})")
    twice = [p for p, s in ctx.bind_stamps.items() if len(s) > 1]
    if twice:
        faults.append(f"{len(twice)} pods got more than one Binding (first: {twice[0]})")
    ctx.facts["binding"] = {
        "due": len(ctx.due), "unbound": len(missing),
        "bound": len(ctx.bind_stamps), "bound_twice": len(twice),
    }
    return faults
