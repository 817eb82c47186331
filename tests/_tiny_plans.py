"""The three tiny CNN plans of the benchmark's tests, lowered to unit
graphs: ``tiny_resnet`` under its full and its pruned plan, and
``tiny_mobilenet`` (plans in ``tests/bench/data``)."""
import os

import jax

from repro.core.plan import CompressionPlan
from repro.models import cnn, cnn_host, zoo

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "bench",
                    "data")
PLANS = [("tiny_resnet", "tiny_resnet.plan.json"),
         ("tiny_resnet", "tiny_resnet_pruned.plan.json"),
         ("tiny_mobilenet", "tiny_mobilenet.plan.json")]


def tiny_graph(zoo_name: str, plan_file: str):
    """``(network, unit graph)`` of a zoo network lowered under a plan."""
    net = getattr(zoo, zoo_name)()
    host = cnn_host.CNNHost(net, cnn.init_params(net, jax.random.PRNGKey(0)))
    with open(os.path.join(DATA, plan_file)) as f:
        plan = CompressionPlan.from_json(f.read())
    return net, host.lower_plan(plan)
