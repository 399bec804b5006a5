"""Times ``auto`` chose an XLA path over a Pallas kernel while the
serving programs were built: ``pallas_fallbacks{reason}`` summed."""


def read(facts):
    if facts.get("kind") != "serve":
        return None
    return facts["after"]["pallas_fallbacks"]
