"""Test only: a traffic kind added as a file alone.  All requests due at the start."""

from bench_traffic import mix_block, request_for


def plan(params, config, seed, seconds):
    del seconds
    shape = mix_block(config)[0]
    reqs = [(0.0, request_for(config, shape, seed, i)) for i in range(int(params["requests"]))]
    return {"outstanding": None, "requests": iter(reqs)}
