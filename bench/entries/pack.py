"""Entry ``pack``: ``api.pack`` decisions served in turn, closed loop."""
from bench.drivers import Pack

DRIVER = Pack
