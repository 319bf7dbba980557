"""Backend compiles during set-up (JAX monitoring events): the writhe
programs for every kept and survivor count of the plan, once each."""


def read(r):
    return r.get("setup_compiles")
