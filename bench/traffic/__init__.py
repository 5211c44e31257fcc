"""Traffic mixes (JSON) and the one generator that reads them."""
