"""Required work of one step, one module a learner family, found by a
configuration's `work_model.kind`. `step_work(config) -> dict`."""
