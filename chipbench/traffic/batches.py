"""Traffic kind ``batches``: a training job's feed.  The parameters are the
batch size, the samples held in memory, the loader's workers and the steps
kept in flight; the family's ``make_dataset`` draws the samples from the
seed.  There is no arrival process: the step asks for its next batch."""

from __future__ import annotations


def describe(mix):
    return {"kind": "batches", "batch_size": int(mix["batch_size"]),
            "dataset_samples": int(mix["dataset_samples"]),
            "num_workers": int(mix.get("num_workers", 0)),
            "worker_mode": mix.get("worker_mode", "thread"),
            "steps_in_flight": int(mix.get("steps_in_flight", 2))}
