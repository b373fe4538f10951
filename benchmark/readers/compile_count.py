"""XLA compiles the model node counted inside the window: its count
after the window minus its count before it (the node's own listener,
``telemetry.install_compile_listener``). Should read 0."""


def read(run: dict, args: dict):
    c = run.get("compiles") or {}
    if c.get("before") is None or c.get("after") is None:
        return None
    return c["after"] - c["before"]
