"""Requests answered 200 within their deadline, per second of window."""


def read(r):
    if r.get("kind") != "serve_open_loop":
        return None
    return r["good"] / r["window_s"]
