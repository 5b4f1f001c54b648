"""`by_role`: a pod's priority follows its role in the run, not its class.

scheduler_perf's `PreemptionBasic` makes its pods from two templates: the
`initPods` that fill the cluster (`pod-low-priority.yaml`) and the
`measurePods` that arrive afterwards (`pod-high-priority.yaml`). The
harness draws ONE class mix for the fill, the arrivals and the closing
round (traffic.build_plan), so the class cannot carry the template: half
the fill would be high, half the arrivals low and never bound. The role is
in the pod's id, which the plan fixes: `r<i>` is a resident pod of the
fill, everything else (`p<i>` an arrival, `c<i>` the closing round, `s*`,
`w*`) came afterwards. The two tiers are the configuration's:
`priority_by_role` `{"fill": ..., "measured": ...}`.

Pure, as pods/class_only.py asks: a function of the id and the
configuration; no generator is drawn from, the seed is not read.
"""

from ksched_tpu.cluster.api import PodEvent


def make(pod_id: str, task_class: int, config: dict, seed: int) -> PodEvent:
    role = "fill" if pod_id.startswith("r") else "measured"
    return PodEvent(
        pod_id=pod_id, task_class=task_class, priority=int(config["priority_by_role"][role])
    )
