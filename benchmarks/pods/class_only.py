"""`class_only`: a pod carries its id and its task class, nothing else.

What a configuration gets when it names no `pods` module. A module under
pods/ says what the pods of a deployment carry (requests, priority, input
blocks): `make(pod_id, task_class, config, seed) -> PodEvent`. Two rules.
`make` is a pure function of its four arguments: a check recomputes what
every pod carried from `ctx.plan` and the same call (`ctx.make_pod`). And
it draws from no shared generator, the framework's `ksched_tpu.utils` RNG
least of all: `seed_rng(--seed)` feeds the service's task and job ids, and
one draw from it would shift every id of the run; what a module draws, it
draws from a generator of its own keyed by `(seed, pod_id)`.

It is called at the moment the pod is submitted, never ahead of it:
`PodEvent.received_s` stamps itself at construction, and the round that
admits the pod reads its queue wait from that stamp.
"""

from ksched_tpu.cluster.api import PodEvent


def make(pod_id: str, task_class: int, config: dict, seed: int) -> PodEvent:
    return PodEvent(pod_id=pod_id, task_class=task_class)
