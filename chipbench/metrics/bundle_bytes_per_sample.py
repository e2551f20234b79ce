"""Bundler: bytes on disk of the bundle files published in the window,
per sample they hold."""


def read(r):
    if not r.get("bundle_samples"):
        return None
    return r["bundle_bytes"] / r["bundle_samples"]
