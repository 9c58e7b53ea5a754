"""State slots held by running requests, the mean of the gauge
``dl4j_state_slots_in_use`` over the window (sampled by
``jobs/serve_state_space.py``).  A guard, not a lever: a closed loop of as
many clients as slots holds nearly all of them (128 in ``jamba2.serve-chat``);
a lower reading means admissions wait.  Silent on a program without the
gauge."""


def read(ctx):
    return ctx.obs.get("state_slots_in_use")
