"""Times ``auto`` chose an XLA path over a Pallas kernel while the
training programs were built: ``pallas_fallbacks{reason}`` summed."""


def read(facts):
    if facts.get("kind") != "train":
        return None
    return facts["after"]["pallas_fallbacks"]
