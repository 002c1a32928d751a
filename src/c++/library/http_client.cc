#include "http_client.h"

#include <arpa/inet.h>
#include <netdb.h>
#include <netinet/in.h>
#include <netinet/tcp.h>
#include <sys/socket.h>
#include <sys/uio.h>
#include <unistd.h>

#include <zlib.h>

#include <cstring>
#include <sstream>

namespace tc {

namespace {

std::string
UriEscape(const std::string& s)
{
  std::string out;
  for (unsigned char c : s) {
    if (isalnum(c) || c == '-' || c == '_' || c == '.' || c == '~') {
      out.push_back((char)c);
    } else {
      char buf[4];
      snprintf(buf, sizeof(buf), "%%%02X", c);
      out.append(buf);
    }
  }
  return out;
}

}  // namespace

//==============================================================================
// One keep-alive connection.
//
class HttpConnection {
 public:
  HttpConnection(const std::string& host, int port, const TlsOptions& tls)
      : host_(host), port_(port), tls_opts_(tls), fd_(-1)
  {
  }

  ~HttpConnection() { Close(); }

  void Close()
  {
    if (tls_ != nullptr) {
      tls_->ShutdownNotify();
      tls_.reset();
    }
    if (fd_ >= 0) {
      ::close(fd_);
      fd_ = -1;
    }
  }

  bool IsOpen() const { return fd_ >= 0; }

  Error Connect(uint64_t timeout_us)
  {
    Close();
    struct addrinfo hints, *res = nullptr;
    memset(&hints, 0, sizeof(hints));
    hints.ai_family = AF_UNSPEC;
    hints.ai_socktype = SOCK_STREAM;
    std::string port_str = std::to_string(port_);
    int rc = getaddrinfo(host_.c_str(), port_str.c_str(), &hints, &res);
    if (rc != 0) {
      return Error(
          "failed to resolve " + host_ + ": " + gai_strerror(rc));
    }
    Error err("failed to connect to " + host_ + ":" + port_str);
    for (struct addrinfo* ai = res; ai != nullptr; ai = ai->ai_next) {
      fd_ = socket(ai->ai_family, ai->ai_socktype, ai->ai_protocol);
      if (fd_ < 0) {
        continue;
      }
      if (connect(fd_, ai->ai_addr, ai->ai_addrlen) == 0) {
        err = Error::Success;
        break;
      }
      ::close(fd_);
      fd_ = -1;
    }
    freeaddrinfo(res);
    if (!err.IsOk()) {
      return err;
    }
    int one = 1;
    setsockopt(fd_, IPPROTO_TCP, TCP_NODELAY, &one, sizeof(one));
    SetTimeout(timeout_us);
    if (tls_opts_.enabled) {
      Error tls_err =
          TlsSession::Handshake(&tls_, fd_, tls_opts_, host_);
      if (!tls_err.IsOk()) {
        Close();
        return tls_err;
      }
    }
    return Error::Success;
  }

  void SetTimeout(uint64_t timeout_us)
  {
    if (fd_ < 0) {
      return;
    }
    struct timeval tv;
    if (timeout_us == 0) {
      tv.tv_sec = 300;  // generous default so a dead server can't hang us
      tv.tv_usec = 0;
    } else {
      tv.tv_sec = (time_t)(timeout_us / 1000000);
      tv.tv_usec = (suseconds_t)(timeout_us % 1000000);
    }
    setsockopt(fd_, SOL_SOCKET, SO_RCVTIMEO, &tv, sizeof(tv));
    setsockopt(fd_, SOL_SOCKET, SO_SNDTIMEO, &tv, sizeof(tv));
  }

  Error SendAll(const struct iovec* iov, int iovcnt)
  {
    // writev with continuation across partial writes; TLS sessions take
    // the per-iovec path (SSL_write has no scatter-gather)
    std::vector<struct iovec> vec(iov, iov + iovcnt);
    size_t idx = 0;
    while (idx < vec.size()) {
      ssize_t n;
      if (tls_ != nullptr) {
        n = tls_->Send(vec[idx].iov_base, vec[idx].iov_len);
      } else {
        n = writev(fd_, vec.data() + idx, (int)(vec.size() - idx));
      }
      if (n < 0) {
        if (errno == EINTR) {
          continue;
        }
        return Error(
            std::string("send failed: ") + strerror(errno));
      }
      size_t remaining = (size_t)n;
      while (idx < vec.size() && remaining >= vec[idx].iov_len) {
        remaining -= vec[idx].iov_len;
        ++idx;
      }
      if (idx < vec.size() && remaining > 0) {
        vec[idx].iov_base = (uint8_t*)vec[idx].iov_base + remaining;
        vec[idx].iov_len -= remaining;
      }
    }
    return Error::Success;
  }

  // recv() for whichever transport is live (plain fd or TLS session)
  ssize_t RecvSome(void* buf, size_t len)
  {
    if (tls_ != nullptr) {
      return tls_->Recv(buf, len);
    }
    return recv(fd_, buf, len, 0);
  }

  // Read an HTTP/1.1 response: status code, headers, body (Content-Length
  // or chunked).
  Error ReadResponse(
      long* code, std::map<std::string, std::string>* headers,
      std::string* body, bool* got_bytes = nullptr)
  {
    if (got_bytes != nullptr) {
      *got_bytes = false;
    }
    std::string buf;
    size_t header_end;
    while (true) {
      header_end = buf.find("\r\n\r\n");
      if (header_end != std::string::npos) {
        break;
      }
      char tmp[8192];
      ssize_t n = RecvSome(tmp, sizeof(tmp));
      if (n <= 0) {
        Close();
        return Error(
            n == 0 ? "connection closed while reading response headers"
                   : std::string("recv failed: ") + strerror(errno));
      }
      if (got_bytes != nullptr) {
        *got_bytes = true;
      }
      buf.append(tmp, (size_t)n);
    }
    // status line
    size_t line_end = buf.find("\r\n");
    std::string status_line = buf.substr(0, line_end);
    size_t sp = status_line.find(' ');
    if (sp == std::string::npos) {
      Close();
      return Error("malformed HTTP status line: " + status_line);
    }
    *code = strtol(status_line.c_str() + sp + 1, nullptr, 10);
    // headers
    headers->clear();
    size_t pos = line_end + 2;
    while (pos < header_end) {
      size_t eol = buf.find("\r\n", pos);
      std::string line = buf.substr(pos, eol - pos);
      pos = eol + 2;
      size_t colon = line.find(':');
      if (colon == std::string::npos) {
        continue;
      }
      std::string key = line.substr(0, colon);
      for (auto& c : key) {
        c = (char)tolower((unsigned char)c);
      }
      size_t vstart = colon + 1;
      while (vstart < line.size() && line[vstart] == ' ') {
        ++vstart;
      }
      (*headers)[key] = line.substr(vstart);
    }
    std::string rest = buf.substr(header_end + 4);
    // body
    auto te = headers->find("transfer-encoding");
    if (te != headers->end() && te->second.find("chunked") !=
        std::string::npos) {
      return ReadChunked(rest, body);
    }
    size_t content_length = 0;
    auto cl = headers->find("content-length");
    if (cl != headers->end()) {
      content_length = (size_t)strtoull(cl->second.c_str(), nullptr, 10);
    }
    body->assign(rest);
    while (body->size() < content_length) {
      char tmp[65536];
      size_t want = content_length - body->size();
      ssize_t n = RecvSome(
          tmp, want < sizeof(tmp) ? want : sizeof(tmp));
      if (n <= 0) {
        Close();
        return Error(
            n == 0 ? "connection closed while reading response body"
                   : std::string("recv failed: ") + strerror(errno));
      }
      body->append(tmp, (size_t)n);
    }
    return Error::Success;
  }

 private:
  Error ReadChunked(const std::string& initial, std::string* body)
  {
    std::string buf = initial;
    body->clear();
    size_t pos = 0;
    while (true) {
      // ensure a full chunk-size line
      size_t eol;
      while ((eol = buf.find("\r\n", pos)) == std::string::npos) {
        char tmp[8192];
        ssize_t n = RecvSome(tmp, sizeof(tmp));
        if (n <= 0) {
          Close();
          return Error("connection closed mid chunked body");
        }
        buf.append(tmp, (size_t)n);
      }
      size_t chunk_len =
          (size_t)strtoull(buf.c_str() + pos, nullptr, 16);
      pos = eol + 2;
      if (chunk_len == 0) {
        // consume the (possibly empty) trailer section up to its blank
        // line so the keep-alive connection stays framed
        while (true) {
          size_t teol;
          while ((teol = buf.find("\r\n", pos)) == std::string::npos) {
            char tmp[1024];
            ssize_t n = RecvSome(tmp, sizeof(tmp));
            if (n <= 0) {
              Close();
              return Error("connection closed in chunked trailer");
            }
            buf.append(tmp, (size_t)n);
          }
          bool blank = (teol == pos);
          pos = teol + 2;
          if (blank) {
            return Error::Success;
          }
        }
      }
      while (buf.size() < pos + chunk_len + 2) {
        char tmp[65536];
        ssize_t n = RecvSome(tmp, sizeof(tmp));
        if (n <= 0) {
          Close();
          return Error("connection closed mid chunked body");
        }
        buf.append(tmp, (size_t)n);
      }
      body->append(buf, pos, chunk_len);
      pos += chunk_len + 2;  // skip trailing CRLF
    }
  }

  std::string host_;
  int port_;
  TlsOptions tls_opts_;
  std::unique_ptr<TlsSession> tls_;
  int fd_;
};

//==============================================================================
// Keep-alive connection pool.
//
class HttpConnectionPool {
 public:
  HttpConnectionPool(
      const std::string& host, int port,
      const TlsOptions& tls = TlsOptions())
      : host_(host), port_(port), tls_(tls)
  {
  }

  std::unique_ptr<HttpConnection> Acquire()
  {
    std::lock_guard<std::mutex> lk(mu_);
    if (!idle_.empty()) {
      auto conn = std::move(idle_.back());
      idle_.pop_back();
      return conn;
    }
    return std::unique_ptr<HttpConnection>(
        new HttpConnection(host_, port_, tls_));
  }

  void Release(std::unique_ptr<HttpConnection> conn)
  {
    if (conn && conn->IsOpen()) {
      std::lock_guard<std::mutex> lk(mu_);
      idle_.push_back(std::move(conn));
    }
  }

 private:
  std::string host_;
  int port_;
  TlsOptions tls_;
  std::mutex mu_;
  std::vector<std::unique_ptr<HttpConnection>> idle_;
};

//==============================================================================
// HTTP result implementation.
//
class InferResultHttp : public InferResult {
 public:
  static Error Create(
      InferResult** result, std::shared_ptr<std::string> body,
      size_t header_length);

  Error ModelName(std::string* name) const override
  {
    return GetString("model_name", name);
  }
  Error ModelVersion(std::string* version) const override
  {
    return GetString("model_version", version);
  }
  Error Id(std::string* id) const override { return GetString("id", id); }

  Error Shape(
      const std::string& output_name,
      std::vector<int64_t>* shape) const override
  {
    auto out = FindOutput(output_name);
    if (out == nullptr) {
      return Error("output '" + output_name + "' not found");
    }
    shape->clear();
    auto shape_val = out->Get("shape");
    if (shape_val != nullptr) {
      for (const auto& d : shape_val->Elements()) {
        shape->push_back(d->AsInt());
      }
    }
    return Error::Success;
  }

  Error Datatype(
      const std::string& output_name, std::string* datatype) const override
  {
    auto out = FindOutput(output_name);
    if (out == nullptr) {
      return Error("output '" + output_name + "' not found");
    }
    auto dt = out->Get("datatype");
    *datatype = dt ? dt->AsString() : "";
    return Error::Success;
  }

  Error RawData(
      const std::string& output_name, const uint8_t** buf,
      size_t* byte_size) const override
  {
    auto it = raw_outputs_.find(output_name);
    if (it == raw_outputs_.end()) {
      return Error(
          "output '" + output_name + "' has no binary data");
    }
    *buf = it->second.first;
    *byte_size = it->second.second;
    return Error::Success;
  }

  Error StringData(
      const std::string& output_name,
      std::vector<std::string>* string_result) const override
  {
    const uint8_t* buf;
    size_t byte_size;
    Error err = RawData(output_name, &buf, &byte_size);
    if (!err.IsOk()) {
      return err;
    }
    string_result->clear();
    size_t pos = 0;
    while (pos + 4 <= byte_size) {
      uint32_t len;
      memcpy(&len, buf + pos, 4);
      pos += 4;
      if (pos + len > byte_size) {
        return Error("malformed BYTES tensor in output " + output_name);
      }
      string_result->emplace_back(
          reinterpret_cast<const char*>(buf + pos), len);
      pos += len;
    }
    return Error::Success;
  }

  std::string DebugString() const override
  {
    return header_ ? header_->Serialize() : "{}";
  }

  Error RequestStatus() const override { return status_; }

 private:
  Error GetString(const char* key, std::string* out) const
  {
    auto v = header_ ? header_->Get(key) : nullptr;
    *out = v ? v->AsString() : "";
    return Error::Success;
  }

  json::ValuePtr FindOutput(const std::string& name) const
  {
    auto outputs = header_ ? header_->Get("outputs") : nullptr;
    if (outputs == nullptr) {
      return nullptr;
    }
    for (const auto& out : outputs->Elements()) {
      auto n = out->Get("name");
      if (n != nullptr && n->AsString() == name) {
        return out;
      }
    }
    return nullptr;
  }

  std::shared_ptr<std::string> body_;
  json::ValuePtr header_;
  Error status_;
  // name -> (ptr into body_, len)
  std::map<std::string, std::pair<const uint8_t*, size_t>> raw_outputs_;
};

Error
InferResultHttp::Create(
    InferResult** result, std::shared_ptr<std::string> body,
    size_t header_length)
{
  auto* res = new InferResultHttp();
  res->body_ = body;
  size_t json_len = header_length ? header_length : body->size();
  std::string err_str;
  res->header_ = json::Parse(body->substr(0, json_len), &err_str);
  if (res->header_ == nullptr) {
    delete res;
    return Error("failed to parse inference response JSON: " + err_str);
  }
  if (res->header_->Has("error")) {
    res->status_ = Error(res->header_->Get("error")->AsString());
  }
  // map binary sections: outputs in order, each with binary_data_size param
  size_t offset = json_len;
  auto outputs = res->header_->Get("outputs");
  if (outputs != nullptr) {
    for (const auto& out : outputs->Elements()) {
      auto params = out->Get("parameters");
      auto name = out->Get("name");
      if (params != nullptr && params->Has("binary_data_size") &&
          name != nullptr) {
        size_t size = (size_t)params->Get("binary_data_size")->AsInt();
        if (offset + size > body->size()) {
          delete res;
          return Error("binary output section exceeds response body");
        }
        res->raw_outputs_[name->AsString()] = {
            reinterpret_cast<const uint8_t*>(body->data()) + offset, size};
        offset += size;
      }
    }
  }
  *result = res;
  return Error::Success;
}

//==============================================================================

Error
InferenceServerHttpClient::Create(
    std::unique_ptr<InferenceServerHttpClient>* client,
    const std::string& server_url, bool verbose, int concurrency,
    const HttpSslOptions& ssl_options)
{
  if (server_url.rfind("https://", 0) == 0) {
    std::string why;
    if (!TlsSession::Available(&why)) {
      return Error("https requested but " + why);
    }
  }
  // the transport loads certificates with the *_PEM loaders only; a
  // DER request must fail here rather than be silently parsed as PEM
  if (ssl_options.cert_type != HttpSslOptions::CERTTYPE::CERT_PEM) {
    return Error("unsupported ssl certificate type: only PEM is supported");
  }
  if (ssl_options.key_type != HttpSslOptions::KEYTYPE::KEY_PEM) {
    return Error("unsupported ssl key type: only PEM is supported");
  }
  client->reset(new InferenceServerHttpClient(
      server_url, verbose, concurrency, ssl_options));
  return Error::Success;
}

InferenceServerHttpClient::InferenceServerHttpClient(
    const std::string& url, bool verbose, int concurrency,
    const HttpSslOptions& ssl_options)
    : InferenceServerClient(verbose)
{
  // TLS iff the URL carries the https scheme (reference semantics:
  // SetSSLCurlOptions applies to an https:// URL,
  // reference http_client.cc:253-280)
  bool use_tls = url.rfind("https://", 0) == 0;
  std::string stripped = url;
  auto scheme = stripped.find("://");
  if (scheme != std::string::npos) {
    stripped = stripped.substr(scheme + 3);
  }
  auto colon = stripped.rfind(':');
  if (colon == std::string::npos) {
    host_ = stripped;
    port_ = use_tls ? 443 : 8000;
  } else {
    host_ = stripped.substr(0, colon);
    port_ = atoi(stripped.c_str() + colon + 1);
  }
  TlsOptions tls;
  tls.enabled = use_tls;
  tls.ca_file = ssl_options.ca_info;
  tls.cert_file = ssl_options.cert;
  tls.key_file = ssl_options.key;
  tls.verify_peer = ssl_options.verify_peer != 0;
  tls.verify_host = ssl_options.verify_host != 0;
  pool_.reset(new HttpConnectionPool(host_, port_, tls));
  for (int i = 0; i < concurrency; ++i) {
    workers_.emplace_back(&InferenceServerHttpClient::AsyncWorker, this);
  }
}

InferenceServerHttpClient::~InferenceServerHttpClient()
{
  {
    std::lock_guard<std::mutex> lk(async_mu_);
    exiting_ = true;
  }
  async_cv_.notify_all();
  for (auto& w : workers_) {
    w.join();
  }
}

void
InferenceServerHttpClient::AsyncWorker()
{
  while (true) {
    std::function<void()> job;
    {
      std::unique_lock<std::mutex> lk(async_mu_);
      async_cv_.wait(
          lk, [this] { return exiting_ || !async_queue_.empty(); });
      if (exiting_ && async_queue_.empty()) {
        return;
      }
      job = std::move(async_queue_.front());
      async_queue_.pop_front();
    }
    job();
  }
}

//==============================================================================
// plumbing

Error
InferenceServerHttpClient::Get(
    const std::string& path, long* http_code, std::string* response)
{
  auto conn = pool_->Acquire();
  Error err;
  for (int attempt = 0; attempt < 2; ++attempt) {
    if (!conn->IsOpen()) {
      err = conn->Connect(0);
      if (!err.IsOk()) {
        return err;
      }
    }
    std::ostringstream req;
    req << "GET " << path << " HTTP/1.1\r\nHost: " << host_
        << "\r\nConnection: keep-alive\r\n\r\n";
    std::string header = req.str();
    struct iovec iov{(void*)header.data(), header.size()};
    err = conn->SendAll(&iov, 1);
    if (!err.IsOk()) {
      conn->Close();
      continue;  // stale keep-alive connection: retry once fresh
    }
    std::map<std::string, std::string> headers;
    err = conn->ReadResponse(http_code, &headers, response);
    if (err.IsOk()) {
      break;
    }
    conn->Close();
  }
  if (verbose_ && err.IsOk()) {
    printf("GET %s -> %ld\n%s\n", path.c_str(), *http_code,
           response->c_str());
  }
  pool_->Release(std::move(conn));
  return err;
}

Error
InferenceServerHttpClient::Post(
    const std::string& path, const std::string& body, long* http_code,
    std::string* response,
    const std::map<std::string, std::string>& extra_headers)
{
  auto conn = pool_->Acquire();
  Error err;
  for (int attempt = 0; attempt < 2; ++attempt) {
    if (!conn->IsOpen()) {
      err = conn->Connect(0);
      if (!err.IsOk()) {
        return err;
      }
    }
    std::ostringstream req;
    req << "POST " << path << " HTTP/1.1\r\nHost: " << host_
        << "\r\nConnection: keep-alive\r\nContent-Type: application/json"
        << "\r\nContent-Length: " << body.size() << "\r\n";
    for (const auto& kv : extra_headers) {
      req << kv.first << ": " << kv.second << "\r\n";
    }
    req << "\r\n";
    std::string header = req.str();
    struct iovec iov[2] = {
        {(void*)header.data(), header.size()},
        {(void*)body.data(), body.size()},
    };
    err = conn->SendAll(iov, body.empty() ? 1 : 2);
    if (!err.IsOk()) {
      conn->Close();
      continue;
    }
    std::map<std::string, std::string> headers;
    err = conn->ReadResponse(http_code, &headers, response);
    if (err.IsOk()) {
      break;
    }
    conn->Close();
  }
  if (verbose_ && err.IsOk()) {
    printf("POST %s -> %ld\n%s\n", path.c_str(), *http_code,
           response->c_str());
  }
  pool_->Release(std::move(conn));
  return err;
}


namespace {

// zlib-backed body (de)compression for the gzip/deflate content codings
// (role of reference http_client.cc:563-580 CompressInput, which rides
// libcurl; windowBits 15+16 selects the gzip wrapper).
Error
CompressBuffer(
    const std::string& algorithm, const std::vector<uint8_t>& in,
    std::vector<uint8_t>* out)
{
  z_stream strm{};
  int window_bits = (algorithm == "gzip") ? 15 + 16 : 15;
  if (deflateInit2(
          &strm, Z_DEFAULT_COMPRESSION, Z_DEFLATED, window_bits, 8,
          Z_DEFAULT_STRATEGY) != Z_OK) {
    return Error("unable to initialize " + algorithm + " compression");
  }
  out->resize(deflateBound(&strm, in.size()));
  // feed in <4 GiB chunks: zlib's avail_in/avail_out are 32-bit
  const size_t kChunk = 1u << 30;
  size_t consumed = 0;
  size_t produced = 0;
  int rc = Z_OK;
  do {
    size_t in_chunk = std::min(kChunk, in.size() - consumed);
    strm.next_in = (Bytef*)in.data() + consumed;
    strm.avail_in = (uInt)in_chunk;
    bool last = (consumed + in_chunk == in.size());
    do {
      size_t out_chunk = std::min(kChunk, out->size() - produced);
      strm.next_out = out->data() + produced;
      strm.avail_out = (uInt)out_chunk;
      rc = deflate(&strm, last ? Z_FINISH : Z_NO_FLUSH);
      produced += out_chunk - strm.avail_out;
    } while (rc == Z_OK && strm.avail_in > 0);
    consumed += in_chunk - strm.avail_in;
  } while (rc == Z_OK && consumed < in.size());
  deflateEnd(&strm);
  if (rc != Z_STREAM_END) {
    return Error(algorithm + " compression failed");
  }
  out->resize(produced);
  return Error::Success;
}

Error
DecompressString(const std::string& encoding, std::string* body)
{
  z_stream strm{};
  // 15+32: auto-detect gzip or zlib wrapper
  if (inflateInit2(&strm, 15 + 32) != Z_OK) {
    return Error("unable to initialize " + encoding + " decompression");
  }
  std::string out;
  out.resize(body->size() * 4 + 1024);
  const size_t kChunk = 1u << 30;  // zlib counters are 32-bit
  size_t consumed = 0;
  size_t written = 0;
  int rc = Z_OK;
  do {
    size_t in_chunk = std::min(kChunk, body->size() - consumed);
    strm.next_in = (Bytef*)body->data() + consumed;
    strm.avail_in = (uInt)in_chunk;
    do {
      if (written == out.size()) {
        out.resize(out.size() * 2);
      }
      size_t out_chunk = std::min(kChunk, out.size() - written);
      strm.next_out = (Bytef*)out.data() + written;
      strm.avail_out = (uInt)out_chunk;
      rc = inflate(&strm, Z_NO_FLUSH);
      written += out_chunk - strm.avail_out;
    } while (rc == Z_OK && strm.avail_in > 0);
    consumed += in_chunk - strm.avail_in;
  } while (rc == Z_OK && consumed < body->size());
  inflateEnd(&strm);
  if (rc != Z_STREAM_END) {
    return Error(encoding + " decompression failed");
  }
  out.resize(written);
  *body = std::move(out);
  return Error::Success;
}

}  // namespace

Error
InferenceServerHttpClient::PostBinary(
    const std::string& path, const std::vector<uint8_t>& body,
    size_t header_length, long* http_code, std::string* response,
    size_t* response_header_length, uint64_t timeout_us,
    const std::string& extra_headers, std::string* response_content_encoding)
{
  auto conn = pool_->Acquire();
  Error err;
  std::map<std::string, std::string> resp_headers;
  for (int attempt = 0; attempt < 2; ++attempt) {
    bool reused = conn->IsOpen();
    if (!reused) {
      err = conn->Connect(timeout_us);
      if (!err.IsOk()) {
        return err;
      }
    } else {
      conn->SetTimeout(timeout_us);
    }
    std::ostringstream req;
    req << "POST " << path << " HTTP/1.1\r\nHost: " << host_
        << "\r\nConnection: keep-alive"
        << "\r\nContent-Type: application/octet-stream"
        << "\r\nInference-Header-Content-Length: " << header_length
        << extra_headers
        << "\r\nContent-Length: " << body.size() << "\r\n\r\n";
    std::string header = req.str();
    struct iovec iov[2] = {
        {(void*)header.data(), header.size()},
        {(void*)body.data(), body.size()},
    };
    err = conn->SendAll(iov, 2);
    if (!err.IsOk()) {
      conn->Close();
      if (reused) {
        continue;  // stale keep-alive connection detected at send
      }
      break;
    }
    bool got_bytes = false;
    err = conn->ReadResponse(http_code, &resp_headers, response,
                             &got_bytes);
    if (err.IsOk()) {
      break;
    }
    conn->Close();
    // Inference POSTs are not idempotent (sequences, KV-cache state):
    // only resend when a reused connection died before delivering ANY
    // response bytes — the classic stale keep-alive race, where the
    // server closed before our request arrived.
    if (!(reused && !got_bytes)) {
      break;
    }
  }
  if (err.IsOk()) {
    auto it = resp_headers.find("inference-header-content-length");
    *response_header_length =
        it == resp_headers.end()
            ? 0
            : (size_t)strtoull(it->second.c_str(), nullptr, 10);
    if (response_content_encoding != nullptr) {
      auto enc = resp_headers.find("content-encoding");
      *response_content_encoding =
          enc == resp_headers.end() ? "" : enc->second;
    }
  }
  pool_->Release(std::move(conn));
  return err;
}

namespace {

Error
CheckJsonResponse(long code, const std::string& body)
{
  if (code >= 400) {
    std::string err_str;
    auto doc = json::Parse(body, &err_str);
    if (doc != nullptr && doc->Has("error")) {
      return Error(doc->Get("error")->AsString());
    }
    return Error("HTTP " + std::to_string(code) + ": " + body);
  }
  return Error::Success;
}

}  // namespace

//==============================================================================
// API surface

Error
InferenceServerHttpClient::IsServerLive(bool* live)
{
  long code;
  std::string body;
  Error err = Get("/v2/health/live", &code, &body);
  *live = err.IsOk() && code == 200;
  return err;
}

Error
InferenceServerHttpClient::IsServerReady(bool* ready)
{
  long code;
  std::string body;
  Error err = Get("/v2/health/ready", &code, &body);
  *ready = err.IsOk() && code == 200;
  return err;
}

Error
InferenceServerHttpClient::IsModelReady(
    bool* ready, const std::string& model_name,
    const std::string& model_version)
{
  std::string path = "/v2/models/" + UriEscape(model_name);
  if (!model_version.empty()) {
    path += "/versions/" + model_version;
  }
  long code;
  std::string body;
  Error err = Get(path + "/ready", &code, &body);
  *ready = err.IsOk() && code == 200;
  return err;
}

Error
InferenceServerHttpClient::ServerMetadata(std::string* server_metadata)
{
  long code;
  Error err = Get("/v2", &code, server_metadata);
  if (!err.IsOk()) {
    return err;
  }
  return CheckJsonResponse(code, *server_metadata);
}

Error
InferenceServerHttpClient::ModelMetadata(
    std::string* model_metadata, const std::string& model_name,
    const std::string& model_version)
{
  std::string path = "/v2/models/" + UriEscape(model_name);
  if (!model_version.empty()) {
    path += "/versions/" + model_version;
  }
  long code;
  Error err = Get(path, &code, model_metadata);
  if (!err.IsOk()) {
    return err;
  }
  return CheckJsonResponse(code, *model_metadata);
}

Error
InferenceServerHttpClient::ModelConfig(
    std::string* model_config, const std::string& model_name,
    const std::string& model_version)
{
  std::string path = "/v2/models/" + UriEscape(model_name);
  if (!model_version.empty()) {
    path += "/versions/" + model_version;
  }
  long code;
  Error err = Get(path + "/config", &code, model_config);
  if (!err.IsOk()) {
    return err;
  }
  return CheckJsonResponse(code, *model_config);
}

Error
InferenceServerHttpClient::ModelRepositoryIndex(std::string* repository_index)
{
  long code;
  Error err = Post("/v2/repository/index", "", &code, repository_index);
  if (!err.IsOk()) {
    return err;
  }
  return CheckJsonResponse(code, *repository_index);
}

Error
InferenceServerHttpClient::LoadModel(
    const std::string& model_name, const std::string& config)
{
  std::string body;
  if (!config.empty()) {
    auto doc = json::Value::MakeObject();
    auto params = json::Value::MakeObject();
    params->Set("config", config);
    doc->Set("parameters", params);
    body = doc->Serialize();
  }
  long code;
  std::string response;
  Error err = Post(
      "/v2/repository/models/" + UriEscape(model_name) + "/load", body,
      &code, &response);
  if (!err.IsOk()) {
    return err;
  }
  return CheckJsonResponse(code, response);
}

Error
InferenceServerHttpClient::UnloadModel(const std::string& model_name)
{
  long code;
  std::string response;
  Error err = Post(
      "/v2/repository/models/" + UriEscape(model_name) + "/unload", "",
      &code, &response);
  if (!err.IsOk()) {
    return err;
  }
  return CheckJsonResponse(code, response);
}

Error
InferenceServerHttpClient::ModelInferenceStatistics(
    std::string* infer_stat, const std::string& model_name,
    const std::string& model_version)
{
  std::string path = "/v2/models/stats";
  if (!model_name.empty()) {
    path = "/v2/models/" + UriEscape(model_name);
    if (!model_version.empty()) {
      path += "/versions/" + model_version;
    }
    path += "/stats";
  }
  long code;
  Error err = Get(path, &code, infer_stat);
  if (!err.IsOk()) {
    return err;
  }
  return CheckJsonResponse(code, *infer_stat);
}

Error
InferenceServerHttpClient::UpdateTraceSettings(
    std::string* response, const std::string& model_name,
    const std::map<std::string, std::vector<std::string>>& settings)
{
  auto doc = json::Value::MakeObject();
  for (const auto& kv : settings) {
    if (kv.second.size() == 1) {
      doc->Set(kv.first, kv.second[0]);
    } else {
      auto arr = json::Value::MakeArray();
      for (const auto& v : kv.second) {
        arr->Append(std::make_shared<json::Value>(v));
      }
      doc->Set(kv.first, arr);
    }
  }
  std::string path = model_name.empty()
                         ? "/v2/trace/setting"
                         : "/v2/models/" + UriEscape(model_name) +
                               "/trace/setting";
  long code;
  Error err = Post(path, doc->Serialize(), &code, response);
  if (!err.IsOk()) {
    return err;
  }
  return CheckJsonResponse(code, *response);
}

Error
InferenceServerHttpClient::GetTraceSettings(
    std::string* settings, const std::string& model_name)
{
  std::string path = model_name.empty()
                         ? "/v2/trace/setting"
                         : "/v2/models/" + UriEscape(model_name) +
                               "/trace/setting";
  long code;
  Error err = Get(path, &code, settings);
  if (!err.IsOk()) {
    return err;
  }
  return CheckJsonResponse(code, *settings);
}

Error
InferenceServerHttpClient::UpdateLogSettings(
    std::string* response, const std::string& settings_json)
{
  long code;
  Error err = Post("/v2/logging", settings_json, &code, response);
  if (!err.IsOk()) {
    return err;
  }
  return CheckJsonResponse(code, *response);
}

Error
InferenceServerHttpClient::GetLogSettings(std::string* settings)
{
  long code;
  Error err = Get("/v2/logging", &code, settings);
  if (!err.IsOk()) {
    return err;
  }
  return CheckJsonResponse(code, *settings);
}

Error
InferenceServerHttpClient::RegisterSystemSharedMemory(
    const std::string& name, const std::string& key, size_t byte_size,
    size_t offset)
{
  auto doc = json::Value::MakeObject();
  doc->Set("key", key);
  doc->Set("offset", (int64_t)offset);
  doc->Set("byte_size", (int64_t)byte_size);
  long code;
  std::string response;
  Error err = Post(
      "/v2/systemsharedmemory/region/" + UriEscape(name) + "/register",
      doc->Serialize(), &code, &response);
  if (!err.IsOk()) {
    return err;
  }
  return CheckJsonResponse(code, response);
}

Error
InferenceServerHttpClient::UnregisterSystemSharedMemory(
    const std::string& name)
{
  std::string path = name.empty()
                         ? "/v2/systemsharedmemory/unregister"
                         : "/v2/systemsharedmemory/region/" +
                               UriEscape(name) + "/unregister";
  long code;
  std::string response;
  Error err = Post(path, "", &code, &response);
  if (!err.IsOk()) {
    return err;
  }
  return CheckJsonResponse(code, response);
}

Error
InferenceServerHttpClient::SystemSharedMemoryStatus(std::string* status)
{
  long code;
  Error err = Get("/v2/systemsharedmemory/status", &code, status);
  if (!err.IsOk()) {
    return err;
  }
  return CheckJsonResponse(code, *status);
}

Error
InferenceServerHttpClient::RegisterXlaSharedMemory(
    const std::string& name, const std::string& raw_handle,
    size_t byte_size, int device_ordinal)
{
  auto doc = json::Value::MakeObject();
  auto handle = json::Value::MakeObject();
  handle->Set("b64", raw_handle);
  doc->Set("raw_handle", handle);
  doc->Set("device_ordinal", (int64_t)device_ordinal);
  doc->Set("byte_size", (int64_t)byte_size);
  long code;
  std::string response;
  Error err = Post(
      "/v2/xlasharedmemory/region/" + UriEscape(name) + "/register",
      doc->Serialize(), &code, &response);
  if (!err.IsOk()) {
    return err;
  }
  return CheckJsonResponse(code, response);
}

Error
InferenceServerHttpClient::UnregisterXlaSharedMemory(const std::string& name)
{
  std::string path = name.empty()
                         ? "/v2/xlasharedmemory/unregister"
                         : "/v2/xlasharedmemory/region/" + UriEscape(name) +
                               "/unregister";
  long code;
  std::string response;
  Error err = Post(path, "", &code, &response);
  if (!err.IsOk()) {
    return err;
  }
  return CheckJsonResponse(code, response);
}

Error
InferenceServerHttpClient::XlaSharedMemoryStatus(std::string* status)
{
  long code;
  Error err = Get("/v2/xlasharedmemory/status", &code, status);
  if (!err.IsOk()) {
    return err;
  }
  return CheckJsonResponse(code, *status);
}

Error
InferenceServerHttpClient::RegisterCudaSharedMemory(
    const std::string& name, const std::string& raw_handle,
    size_t byte_size, int device_id)
{
  auto doc = json::Value::MakeObject();
  auto handle = json::Value::MakeObject();
  handle->Set("b64", raw_handle);
  doc->Set("raw_handle", handle);
  doc->Set("device_id", (int64_t)device_id);
  doc->Set("byte_size", (int64_t)byte_size);
  long code;
  std::string response;
  Error err = Post(
      "/v2/cudasharedmemory/region/" + UriEscape(name) + "/register",
      doc->Serialize(), &code, &response);
  if (!err.IsOk()) {
    return err;
  }
  return CheckJsonResponse(code, response);
}

Error
InferenceServerHttpClient::UnregisterCudaSharedMemory(
    const std::string& name)
{
  std::string path = name.empty()
                         ? "/v2/cudasharedmemory/unregister"
                         : "/v2/cudasharedmemory/region/" +
                               UriEscape(name) + "/unregister";
  long code;
  std::string response;
  Error err = Post(path, "", &code, &response);
  if (!err.IsOk()) {
    return err;
  }
  return CheckJsonResponse(code, response);
}

Error
InferenceServerHttpClient::CudaSharedMemoryStatus(std::string* status)
{
  long code;
  Error err = Get("/v2/cudasharedmemory/status", &code, status);
  if (!err.IsOk()) {
    return err;
  }
  return CheckJsonResponse(code, *status);
}

//==============================================================================
// inference

Error
InferenceServerHttpClient::GenerateRequestBody(
    std::vector<uint8_t>* request_body, size_t* header_length,
    const InferOptions& options, const std::vector<InferInput*>& inputs,
    const std::vector<const InferRequestedOutput*>& outputs)
{
  auto doc = json::Value::MakeObject();
  if (!options.request_id_.empty()) {
    doc->Set("id", options.request_id_);
  }
  auto params = json::Value::MakeObject();
  if (options.sequence_id_ != 0) {
    params->Set("sequence_id", (int64_t)options.sequence_id_);
    params->Set("sequence_start", options.sequence_start_);
    params->Set("sequence_end", options.sequence_end_);
  }
  if (options.priority_ != 0) {
    params->Set("priority", (int64_t)options.priority_);
  }
  if (options.server_timeout_us_ != 0) {
    params->Set("timeout", (int64_t)options.server_timeout_us_);
  }
  if (!params->Members().empty()) {
    doc->Set("parameters", params);
  }

  auto inputs_arr = json::Value::MakeArray();
  size_t total_binary = 0;
  for (auto* input : inputs) {
    auto in = json::Value::MakeObject();
    in->Set("name", input->Name());
    in->Set("datatype", input->Datatype());
    auto shape = json::Value::MakeArray();
    for (auto d : input->Shape()) {
      shape->Append(std::make_shared<json::Value>((int64_t)d));
    }
    in->Set("shape", shape);
    auto in_params = json::Value::MakeObject();
    if (input->IsSharedMemory()) {
      in_params->Set("shared_memory_region", input->SharedMemoryName());
      in_params->Set(
          "shared_memory_byte_size",
          (int64_t)input->SharedMemoryByteSize());
      if (input->SharedMemoryOffset() != 0) {
        in_params->Set(
            "shared_memory_offset", (int64_t)input->SharedMemoryOffset());
      }
    } else {
      in_params->Set(
          "binary_data_size", (int64_t)input->TotalByteSize());
      total_binary += input->TotalByteSize();
    }
    in->Set("parameters", in_params);
    inputs_arr->Append(in);
  }
  doc->Set("inputs", inputs_arr);

  if (!outputs.empty()) {
    auto outputs_arr = json::Value::MakeArray();
    for (const auto* output : outputs) {
      auto out = json::Value::MakeObject();
      out->Set("name", output->Name());
      auto out_params = json::Value::MakeObject();
      if (output->IsSharedMemory()) {
        out_params->Set(
            "shared_memory_region", output->SharedMemoryName());
        out_params->Set(
            "shared_memory_byte_size",
            (int64_t)output->SharedMemoryByteSize());
        if (output->SharedMemoryOffset() != 0) {
          out_params->Set(
              "shared_memory_offset",
              (int64_t)output->SharedMemoryOffset());
        }
      } else {
        out_params->Set("binary_data", output->BinaryData());
        if (output->ClassCount() != 0) {
          out_params->Set(
              "classification", (int64_t)output->ClassCount());
        }
      }
      out->Set("parameters", out_params);
      outputs_arr->Append(out);
    }
    doc->Set("outputs", outputs_arr);
  }

  std::string header = doc->Serialize();
  *header_length = header.size();
  request_body->clear();
  request_body->reserve(header.size() + total_binary);
  request_body->insert(request_body->end(), header.begin(), header.end());
  for (auto* input : inputs) {
    if (input->IsSharedMemory()) {
      continue;
    }
    input->PrepareForRequest();
    const uint8_t* buf;
    size_t len;
    bool end_of_input = false;
    while (!end_of_input) {
      input->GetNext(&buf, &len, &end_of_input);
      if (buf != nullptr && len > 0) {
        request_body->insert(request_body->end(), buf, buf + len);
      }
    }
  }
  return Error::Success;
}

Error
InferenceServerHttpClient::ParseResponseBody(
    InferResult** result, const std::vector<uint8_t>& response_body,
    size_t header_length)
{
  auto shared = std::make_shared<std::string>(
      reinterpret_cast<const char*>(response_body.data()),
      response_body.size());
  return InferResultHttp::Create(result, shared, header_length);
}

Error
InferenceServerHttpClient::Infer(
    InferResult** result, const InferOptions& options,
    const std::vector<InferInput*>& inputs,
    const std::vector<const InferRequestedOutput*>& outputs,
    const std::string& request_compression_algorithm,
    const std::string& response_compression_algorithm)
{
  RequestTimers timer;
  timer.CaptureTimestamp(RequestTimers::Kind::REQUEST_START);

  std::vector<uint8_t> body;
  size_t header_length;
  Error err = GenerateRequestBody(
      &body, &header_length, options, inputs, outputs);
  if (!err.IsOk()) {
    return err;
  }
  return SendInfer(
      result, options, std::move(body), header_length, timer,
      request_compression_algorithm, response_compression_algorithm);
}

Error
InferenceServerHttpClient::SendInfer(
    InferResult** result, const InferOptions& options,
    std::vector<uint8_t> body, size_t header_length, RequestTimers timer,
    const std::string& request_compression_algorithm,
    const std::string& response_compression_algorithm)
{
  Error err;
  std::string extra_headers;
  if (!request_compression_algorithm.empty()) {
    if (request_compression_algorithm != "gzip" &&
        request_compression_algorithm != "deflate") {
      return Error(
          "unsupported request compression algorithm: " +
          request_compression_algorithm);
    }
    std::vector<uint8_t> compressed;
    err = CompressBuffer(request_compression_algorithm, body, &compressed);
    if (!err.IsOk()) {
      return err;
    }
    body = std::move(compressed);
    extra_headers +=
        "\r\nContent-Encoding: " + request_compression_algorithm;
  }
  if (!response_compression_algorithm.empty()) {
    if (response_compression_algorithm != "gzip" &&
        response_compression_algorithm != "deflate") {
      return Error(
          "unsupported response compression algorithm: " +
          response_compression_algorithm);
    }
    extra_headers +=
        "\r\nAccept-Encoding: " + response_compression_algorithm;
  }

  std::string path = "/v2/models/" + UriEscape(options.model_name_);
  if (!options.model_version_.empty()) {
    path += "/versions/" + options.model_version_;
  }
  path += "/infer";

  timer.CaptureTimestamp(RequestTimers::Kind::SEND_START);
  long code;
  std::string response;
  size_t response_header_length;
  std::string response_encoding;
  err = PostBinary(
      path, body, header_length, &code, &response,
      &response_header_length, options.client_timeout_us_,
      extra_headers, &response_encoding);
  timer.CaptureTimestamp(RequestTimers::Kind::SEND_END);
  if (!err.IsOk()) {
    return err;
  }
  if (!response_encoding.empty()) {
    err = DecompressString(response_encoding, &response);
    if (!err.IsOk()) {
      return err;
    }
  }

  timer.CaptureTimestamp(RequestTimers::Kind::RECV_START);
  // move, don't copy: for big tensor responses this is the hot path
  auto shared = std::make_shared<std::string>(std::move(response));
  err = InferResultHttp::Create(result, shared, response_header_length);
  timer.CaptureTimestamp(RequestTimers::Kind::RECV_END);
  timer.CaptureTimestamp(RequestTimers::Kind::REQUEST_END);
  if (!err.IsOk()) {
    return err;
  }
  if (code >= 400 && (*result)->RequestStatus().IsOk()) {
    delete *result;
    *result = nullptr;
    return Error("HTTP " + std::to_string(code) + ": " + *shared);
  }
  UpdateInferStat(timer);
  if (verbose_) {
    printf("infer %s -> %s\n", options.model_name_.c_str(),
           (*result)->DebugString().c_str());
  }
  return Error::Success;
}

Error
InferenceServerHttpClient::AsyncInfer(
    OnCompleteFn callback, const InferOptions& options,
    const std::vector<InferInput*>& inputs,
    const std::vector<const InferRequestedOutput*>& outputs,
    const std::string& request_compression_algorithm,
    const std::string& response_compression_algorithm)
{
  if (callback == nullptr) {
    return Error("callback must not be null for AsyncInfer");
  }
  // The body is serialized here, on the caller's thread, as the
  // reference does: the inputs' read cursors are not safe to walk from
  // several workers at once when requests share an InferInput.
  RequestTimers timer;
  timer.CaptureTimestamp(RequestTimers::Kind::REQUEST_START);
  std::vector<uint8_t> body;
  size_t header_length;
  Error build_err = GenerateRequestBody(
      &body, &header_length, options, inputs, outputs);
  if (!build_err.IsOk()) {
    return build_err;
  }
  InferOptions opts = options;
  std::string req_comp = request_compression_algorithm;
  std::string resp_comp = response_compression_algorithm;
  {
    std::lock_guard<std::mutex> lk(async_mu_);
    if (exiting_) {
      return Error("client is shutting down");
    }
    async_queue_.emplace_back([this, callback, opts,
                               body = std::move(body), header_length,
                               timer, req_comp, resp_comp]() mutable {
      InferResult* result = nullptr;
      Error err = SendInfer(
          &result, opts, std::move(body), header_length, timer, req_comp,
          resp_comp);
      if (!err.IsOk() && result == nullptr) {
        // surface transport failure through a result-less sentinel: the
        // reference delivers a result whose RequestStatus is the error
        class ErrorResult : public InferResult {
         public:
          explicit ErrorResult(const Error& e) : err_(e) {}
          Error ModelName(std::string* v) const override
          {
            v->clear();
            return err_;
          }
          Error ModelVersion(std::string* v) const override
          {
            v->clear();
            return err_;
          }
          Error Id(std::string* v) const override
          {
            v->clear();
            return err_;
          }
          Error Shape(const std::string&, std::vector<int64_t>* s)
              const override
          {
            s->clear();
            return err_;
          }
          Error Datatype(const std::string&, std::string* d) const override
          {
            d->clear();
            return err_;
          }
          Error RawData(const std::string&, const uint8_t** b, size_t* n)
              const override
          {
            *b = nullptr;
            *n = 0;
            return err_;
          }
          Error StringData(const std::string&, std::vector<std::string>* r)
              const override
          {
            r->clear();
            return err_;
          }
          std::string DebugString() const override
          {
            return err_.Message();
          }
          Error RequestStatus() const override { return err_; }

         private:
          Error err_;
        };
        result = new ErrorResult(err);
      }
      callback(result);
    });
  }
  async_cv_.notify_one();
  return Error::Success;
}

}  // namespace tc
