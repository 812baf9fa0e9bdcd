"""How each campaign kind runs a tile and checks its outputs."""
