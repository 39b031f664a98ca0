"""Device time of the polyphase interleave (ops under the program's
``dwt.from_planes`` scope in ``jit_dwt_inverse``) over device busy
time, in the traced window."""
from bench import scopes


def read(ctx):
    return scopes.share(ctx, "jit_dwt_inverse", "dwt.from_planes")
