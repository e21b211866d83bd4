"""General traffic generators, one per kind of traffic; a mix in
``bench/traffic/<name>.json`` names its driver and holds its parameters."""
