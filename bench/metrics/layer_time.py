"""Shared by the per-layer device-time readers."""


def per_request_ms(ctx, layer: str):
    p = ctx.profile
    if not p or not p["requests"]:
        return None
    return 1e3 * p["layers_s"][layer] / p["requests"]
