"""Spin updates offered by the completed solves over the whole window:
replicas × steps × one spin per single-flip step, divided by all elapsed
window time."""


def read(run):
    return len(run.solves) * run.replicas * run.anneal_steps / run.window_s
