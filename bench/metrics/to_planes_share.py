"""Device time of the polyphase split (ops under the program's
``dwt.to_planes`` scope in ``jit_dwt_forward``) over device busy time,
in the traced window."""
from bench import scopes


def read(ctx):
    return scopes.share(ctx, "jit_dwt_forward", "dwt.to_planes")
