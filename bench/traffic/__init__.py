"""Traffic mixes (data) and their generators (kinds/)."""
