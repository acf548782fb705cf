"""Launchers: the APSP serving drivers, the engine pool and the distributed
APSP runner."""
