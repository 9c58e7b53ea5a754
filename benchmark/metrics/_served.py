"""What the serving window processed, from the clients' token timestamps:
the prompts whose first token arrived in the window, and the position of
every decoded token delivered in it."""


def processed(ctx):
    t0, t1 = ctx.obs["t0"], ctx.obs["t1"]
    prompts, positions = [], []
    for r in ctx.obs["records"]:
        for j, t in enumerate(r.times):
            if not t0 <= t < t1:
                continue
            if j == 0:
                prompts.append(r.prompt_len)
            else:       # fed the token at prompt_len + j - 1, attends to it
                positions.append(r.prompt_len + j - 1)
    return prompts, positions
