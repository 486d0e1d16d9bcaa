"""One generator per traffic kind; each mix file names its kind."""
