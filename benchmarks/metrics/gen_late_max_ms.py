"""Load generator: how late the latest request left the client (sent -
due), over the requests due in the window, on the client's clock."""


def read(obs: dict):
    return max(obs["late_ms"]) if obs["late_ms"] else None
