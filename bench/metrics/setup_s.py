"""setup_s (host clock): run start to window start -- graph generation,
server and chip start-up, tenant open (load and upload), tree choice and
weight DPs for the standing pairs, warm-up from the compile cache."""


def read(ctx):
    return ctx.setup_s
