"""Entry ``simulate``: ``api.simulate`` of fleets of consumer groups."""
from bench.drivers import Simulate

DRIVER = Simulate
