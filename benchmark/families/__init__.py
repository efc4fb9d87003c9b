"""One module a model family: what the drivers and readers take from the program for it."""
