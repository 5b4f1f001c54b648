"""`by_request`: a pod's task class IS its size; it carries that size's CPU
and memory requests.

Every pod of a Kubernetes cluster carries `resources.requests`. The
harness draws one class mix for the fill, the arrivals and the closing
round (traffic.build_plan); here the class is an index into the
configuration's table `requests` ([CPU millicores, memory MiB] a class),
and the pod carries that vector as `PodEvent.cpu_request` (in CPUs: 250m is
0.25) and `PodEvent.memory_request` (MiB).

Pure, as pods/class_only.py asks: a function of the class and the
configuration; no generator is drawn from, the seed is not read.
"""

from ksched_tpu.cluster.api import PodEvent


def make(pod_id: str, task_class: int, config: dict, seed: int) -> PodEvent:
    cpu_millis, memory_mib = config["requests"][task_class]
    return PodEvent(
        pod_id=pod_id, task_class=task_class,
        cpu_request=cpu_millis / 1000.0, memory_request=int(memory_mib),
    )
